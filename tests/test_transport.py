"""Transport-layer tests: wire codec, channels, snapshot shipping, payload
fsync, heartbeat liveness (incl. the close/monitor race), re-admission
back-off, the atomic-respawn regression, epoch staleness on the wire, and
partial-send channel poisoning.

Cross-transport behavioral parity (byte-identical manifests/images) lives
in tests/test_sharded_checkpoint.py; SIGKILL crash injection (pipe workers
and socket servers) lives in tests/test_crash_recovery.py; coordinator
failover/takeover lives in tests/test_coordinator_failover.py.
"""
import os
import socket as socket_mod
import threading
import time

import numpy as np
import pytest

from repro.core import EmbShardSpec, ShardedCheckpointWriter, ShardSaveError
from repro.core.transport import (ZEROCOPY_MIN_BYTES, InprocTransport,
                                  PipeEndpoint, ShmSnapshot, SliceSnapshot,
                                  SockChannel, SpoolSnapshot, WriterSession,
                                  _apply_full_payload, _ShardStore,
                                  normalize_transport, pack_msg,
                                  pack_msg_parts, unpack_msg)

SIZES = (40, 17, 3)


def make_state(sizes=SIZES, d=8, seed=0):
    rng = np.random.default_rng(seed)
    tables = [rng.normal(size=(n, d)).astype(np.float32) for n in sizes]
    accs = [np.zeros(n, np.float32) for n in sizes]
    return tables, accs


# ------------------------------------------------------------- codec --------
def test_codec_roundtrips_protocol_values():
    rng = np.random.default_rng(3)
    cases = [
        None, True, False, 0, -1, 2**40, 3.5, float("inf"), "", "drain",
        b"\x00\xffraw", [], (), {}, ("ack", 7, {"kind": "full", "bytes": 12}),
        {"nested": [1, (2, None), {"k": b"v"}]},
        rng.normal(size=(5, 3)).astype(np.float32),
        np.arange(7, dtype=np.int64),
        np.zeros((0, 4), np.float32),          # empty shard slices
        np.float32(1.5), np.int64(9),          # numpy scalars -> python
    ]
    for obj in cases:
        got = unpack_msg(pack_msg(obj))
        if isinstance(obj, np.ndarray):
            assert got.dtype == obj.dtype and got.shape == obj.shape
            np.testing.assert_array_equal(got, obj)
        elif isinstance(obj, np.generic):
            assert got == obj.item()
        else:
            assert got == obj


def test_codec_rejects_unencodable_and_torn_frames():
    with pytest.raises(TypeError):
        pack_msg(object())
    with pytest.raises(ValueError):
        unpack_msg(pack_msg(("x",)) + b"junk")


def test_sock_channel_frames_large_and_interleaved_messages():
    a, b = socket_mod.socketpair()
    ca, cb = SockChannel(a), SockChannel(b)
    big = np.random.default_rng(0).normal(size=(2000, 64)).astype(np.float32)
    msgs = [("full", 1, 0, ("slices", [big], [big[:, 0]])),
            ("drain", 7), ("ping", 1)]

    def sender():
        for m in msgs:
            ca.send(m)
    t = threading.Thread(target=sender)
    t.start()
    got = []
    while len(got) < len(msgs):
        assert cb.poll(5.0)
        got.append(cb.recv())
    t.join()
    assert got[1] == ("drain", 7) and got[2] == ("ping", 1)
    np.testing.assert_array_equal(got[0][3][1][0], big)
    ca.close()
    with pytest.raises(EOFError):
        cb.poll(0.2), cb.recv()
    cb.close()


def test_pack_msg_parts_large_arrays_are_zero_copy():
    """Satellite: large contiguous arrays ride the frame as memoryviews of
    their own buffers — no serialization copy on the submit path."""
    arr = np.arange(ZEROCOPY_MIN_BYTES // 4, dtype=np.float32)  # at threshold
    parts = pack_msg_parts(("rows", arr))
    views = [p for p in parts if isinstance(p, memoryview)]
    assert views, "no zero-copy part emitted for a large array"
    assert any(np.shares_memory(np.frombuffer(v, np.uint8), arr)
               for v in views), "large array payload was copied"
    got = unpack_msg(b"".join(parts))          # joined parts decode as one
    np.testing.assert_array_equal(got[1], arr)
    # below the threshold the copy is cheaper than scatter-gather framing
    small = np.arange(8, dtype=np.int32)
    assert not any(isinstance(p, memoryview)
                   for p in pack_msg_parts(("rows", small)))
    np.testing.assert_array_equal(
        unpack_msg(pack_msg(("rows", small)))[1], small)


def test_sock_channel_codec_compresses_counts_and_interops():
    """Per-frame zlib: large frames shrink on the wire, frames under the
    floor ship raw, and a receiver that never negotiated a codec still
    inflates flagged frames (the high length-prefix bit is stateless)."""
    a, b = socket_mod.socketpair()
    ca, cb = SockChannel(a, codec_level=6), SockChannel(b)  # rx codec-off
    big = np.zeros((4000, 8), np.float32)       # compressible, over floor
    ca.send(("full", 1, big))
    assert cb.poll(5.0)
    got = cb.recv()
    assert got[0] == "full"
    np.testing.assert_array_equal(got[2], big)
    s = ca.wire_stats()
    assert s["wire_sent"] < s["raw_sent"]       # compressed on the wire
    r = cb.wire_stats()
    assert r["raw_rcvd"] == s["raw_sent"]       # inflated back bit-exact
    assert r["wire_rcvd"] == s["wire_sent"]
    # below the size floor the frame ships raw: wire = raw + 8B prefix
    raw0, wire0 = s["raw_sent"], s["wire_sent"]
    ca.send(("ping", 1))
    assert cb.poll(5.0) and cb.recv() == ("ping", 1)
    s2 = ca.wire_stats()
    assert s2["wire_sent"] - wire0 == (s2["raw_sent"] - raw0) + 8
    ca.close()
    cb.close()


def test_normalize_transport_aliases():
    assert normalize_transport("thread") == "inproc"
    assert normalize_transport("process") == "pipe"
    assert normalize_transport("socket") == "socket"
    with pytest.raises(ValueError):
        normalize_transport("carrier-pigeon")


# ------------------------------------------------- snapshot shipping --------
@pytest.mark.parametrize("make_ref", [
    lambda tmp, t, a: ShmSnapshot(5, t, a),
    lambda tmp, t, a: SpoolSnapshot(5, str(tmp), t, a),
])
def test_full_snapshot_payloads_apply_identically(tmp_path, make_ref):
    """shm and spool payloads must produce the exact apply the inline
    arrays would — the worker-side _apply_full_payload is one code path."""
    tables, accs = make_state()
    spec = EmbShardSpec(SIZES, 2)
    ref = make_ref(tmp_path, [t + 3 for t in tables], [a + 3 for a in accs])
    try:
        for j in range(2):
            store = _ShardStore(j, spec, tables, accs)
            _apply_full_payload(store, spec, ref.payload_for(j), step=1,
                                seq=5)
            for t, (lo, hi) in enumerate(store.ranges):
                np.testing.assert_array_equal(store.image_tables[t],
                                              (tables[t] + 3)[lo:hi])
            ev = store.applied[-1]
            assert (ev["kind"], ev["seq"], ev["step"]) == ("full", 5, 1)
    finally:
        ref.release()


def test_shm_snapshot_releases_segment(tmp_path):
    tables, accs = make_state()
    ref = ShmSnapshot(1, tables, accs)
    name = ref._shm.name
    from multiprocessing import shared_memory
    seg = shared_memory.SharedMemory(name=name)   # attachable while pending
    seg.close()
    ref.release()
    with pytest.raises(FileNotFoundError):
        shared_memory.SharedMemory(name=name)


def test_slice_snapshot_sends_only_the_shards_rows():
    tables, accs = make_state()
    spec = EmbShardSpec(SIZES, 4)
    ranges = [[spec.shard_range(t, j) for t in range(len(SIZES))]
              for j in range(4)]
    ref = SliceSnapshot(1, tables, accs, ranges)
    kind, t_slices, a_slices = ref.payload_for(2)
    assert kind == "slices"
    for t, (lo, hi) in enumerate(ranges[2]):
        assert t_slices[t].shape[0] == hi - lo
        np.testing.assert_array_equal(t_slices[t], tables[t][lo:hi])


# -------------------------------------------- power-loss payload fsync ------
def test_drain_fsyncs_payloads_before_ack(tmp_path, monkeypatch):
    """Satellite: the durable watermark must be power-loss-true — every
    payload persisted since the last DRAIN is fsynced (file + directory)
    before the drain ack, not left to the page cache."""
    synced = []
    real_fsync = os.fsync

    def counting_fsync(fd):
        synced.append(os.readlink(f"/proc/self/fd/{fd}"))
        return real_fsync(fd)
    monkeypatch.setattr(os, "fsync", counting_fsync)

    tables, accs = make_state()
    spec = EmbShardSpec(SIZES, 2)
    fleet = ShardedCheckpointWriter(tables, accs, spec,
                                    directory=str(tmp_path),
                                    backend="inproc", delta_saves=False)
    fleet.save_full([t + 1 for t in tables], [a + 1 for a in accs], step=1)
    fleet.save_rows(0, np.arange(4), np.full((4, 8), 2.0, np.float32),
                    np.full(4, 2.0, np.float32), step=2)
    pre_stamp = list(synced)
    assert not any(p.endswith(".npz") for p in pre_stamp), \
        "payload fsync must be batched at DRAIN, not per save"
    fleet.fence()
    # every persisted payload file and its shard directory got synced, and
    # they were synced BEFORE the manifest stamp hit the log
    stamp_at = next(i for i, p in enumerate(synced)
                    if "manifest.json" in p)
    payload_syncs = [p for p in synced[:stamp_at] if p.endswith(".npz")]
    on_disk = [os.path.join(r, f) for r, _, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".npz")]
    assert sorted(payload_syncs) == sorted(on_disk)
    dir_syncs = {p for p in synced[:stamp_at] if "shard_" in p
                 and not p.endswith(".npz")}
    assert dir_syncs            # the directory entries are durable too
    # a second fence with nothing new pending syncs no further payloads
    n = len([p for p in synced if p.endswith(".npz")])
    fleet.fence()
    assert len([p for p in synced if p.endswith(".npz")]) == n
    fleet.close()


def test_fence_fsyncs_dead_shards_acked_payloads(tmp_path, monkeypatch):
    """A shard that died with acked-but-never-drained events: the
    coordinator itself fsyncs those payloads before stamping them."""
    tables, accs = make_state()
    spec = EmbShardSpec(SIZES, 2)
    fleet = ShardedCheckpointWriter(tables, accs, spec,
                                    directory=str(tmp_path),
                                    backend="pipe", delta_saves=False,
                                    drain_timeout=30.0)
    rows = np.arange(4)                          # shard 0 rows
    fleet.save_rows(0, rows, np.full((4, 8), 5.0, np.float32),
                    np.full(4, 5.0, np.float32), step=1)
    # wait until the ack (apply + persist done) is buffered, then kill
    deadline = time.monotonic() + 15.0
    while not fleet.procs[0]._conn.poll(0) and time.monotonic() < deadline:
        time.sleep(0.01)
    assert fleet.procs[0]._conn.poll(0)
    fleet.procs[0].kill()

    synced = []
    real_fsync = os.fsync

    def counting_fsync(fd):
        synced.append(os.readlink(f"/proc/self/fd/{fd}"))
        return real_fsync(fd)
    monkeypatch.setattr(os, "fsync", counting_fsync)
    with pytest.raises(ShardSaveError):
        fleet.fence()
    assert any("shard_0" in p and p.endswith(".npz") for p in synced), \
        "dead shard's stamped payloads were not fsynced by the coordinator"
    fleet.close()


# ------------------------------------------------------- heartbeat ----------
def test_heartbeat_detects_dead_pipe_writer_without_a_save(tmp_path):
    """Satellite: with heartbeat_interval set, a writer that dies between
    saves is latched proactively by the monitor thread — no submit or
    fence required.  (The fold into the poisoned-shard set is owned by the
    trainer thread: check_health / the next routing or fence.)"""
    tables, accs = make_state()
    spec = EmbShardSpec(SIZES, 2)
    fleet = ShardedCheckpointWriter(tables, accs, spec, backend="pipe",
                                    delta_saves=False,
                                    heartbeat_interval=0.05)
    fleet.procs[1].proc.kill()          # die silently, no latch
    deadline = time.monotonic() + 10.0
    while fleet.procs[1].error is None and time.monotonic() < deadline:
        time.sleep(0.02)
    assert fleet.procs[1].error is not None   # latched with no save traffic
    assert "heartbeat" in str(fleet.procs[1].error)
    assert fleet.check_health() == [1]        # trainer-thread fold
    assert 1 in fleet.failed
    assert 0 not in fleet.failed              # only the dead shard poisoned
    fleet.close()


def test_check_health_probes_socket_server(tmp_path):
    """Direct check_health: a SIGKILLed shard server is detected by the
    probe; the severed-connection path is detected by the next probe's
    ping bookkeeping or stream error."""
    tables, accs = make_state()
    spec = EmbShardSpec(SIZES, 2)
    fleet = ShardedCheckpointWriter(tables, accs, spec, backend="socket",
                                    delta_saves=False)
    assert fleet.check_health() == []
    fleet.procs[0]._server_proc.kill()
    fleet.procs[0]._server_proc.join(timeout=5.0)
    assert fleet.check_health() == [0]
    assert 0 in fleet.failed
    fleet.close()


# -------------------------------------------- re-admission back-off ---------
def test_readmit_backoff_throttles_crash_looping_shard():
    """Satellite: with readmit_backoff, a shard that keeps dying is
    re-admitted on an exponential schedule instead of thrashing the fleet;
    a shard that stays healthy through a stamped cycle starts over."""
    tables, accs = make_state()
    spec = EmbShardSpec(SIZES, 2)
    fleet = ShardedCheckpointWriter(tables, accs, spec, backend="inproc",
                                    delta_saves=False,
                                    readmit_backoff=30.0)
    fleet.kill_shard(1)
    assert fleet.readmit(tables, accs, step=1) == [1]   # first: immediate
    fleet.kill_shard(1)                                 # crash loop
    assert fleet.readmit(tables, accs, step=2) == []    # throttled
    assert 1 in fleet.failed                            # still poisoned
    not_before = fleet._readmit_not_before[1]
    assert not_before > time.monotonic()
    # back-off elapses -> eligible again, and the delay doubles
    fleet._readmit_not_before[1] = 0.0
    assert fleet.readmit(tables, accs, step=3) == [1]
    assert (fleet._readmit_not_before[1] - time.monotonic()) > 45.0
    # surviving a stamped cycle resets the attempt counter
    fleet.fence()
    assert fleet._readmit_attempts[1] == 0
    fleet.close()


def test_readmit_without_backoff_retries_every_boundary():
    tables, accs = make_state()
    spec = EmbShardSpec(SIZES, 2)
    fleet = ShardedCheckpointWriter(tables, accs, spec, backend="inproc",
                                    delta_saves=False)
    for k in range(3):
        fleet.kill_shard(0)
        assert fleet.readmit(tables, accs, step=k) == [0]
    assert fleet.shard_readmissions == 3
    fleet.close()


# ------------------------------------------- atomic respawn (regression) ----
def test_failed_respawn_leaves_shard_poisoned_not_half_registered(
        tmp_path, monkeypatch):
    """Regression (satellite bugfix): a respawn that fails mid-way used to
    leave the shard half-registered — latch cleared, dead channel — so
    routing treated it as healthy and saves vanished.  Respawn failure must
    be atomic: the shard stays poisoned, the fleet keeps running, and the
    next boundary's readmit retries successfully."""
    tables, accs = make_state()
    spec = EmbShardSpec(SIZES, 2)
    fleet = ShardedCheckpointWriter(tables, accs, spec,
                                    directory=str(tmp_path), backend="pipe",
                                    delta_saves=False, drain_timeout=30.0)
    fleet.save_full([t + 1 for t in tables], [a + 1 for a in accs], step=1)
    fleet.fence()
    fleet.kill_shard(1)

    boom = RuntimeError("spawn refused")

    def failing_spawn(self, *a, **kw):
        raise boom
    monkeypatch.setattr(PipeEndpoint, "_spawn", failing_spawn)
    assert fleet.readmit([t + 2 for t in tables], [a + 2 for a in accs],
                         step=2) == []
    assert 1 in fleet.failed                       # still out of the fleet
    assert fleet.procs[1].error is not None        # and unambiguously so
    assert fleet.shard_readmissions == 0
    # routing still drops shard 1's work and serves shard 0
    nb = fleet.save_full([t + 3 for t in tables], [a + 3 for a in accs],
                         step=3)
    assert nb > 0 and fleet.dropped_bytes > 0
    with pytest.raises(ShardSaveError):
        fleet.fence()
    # the retry at the next boundary, with spawn working again, succeeds
    monkeypatch.undo()
    assert fleet.readmit([t + 4 for t in tables], [a + 4 for a in accs],
                         step=4) == [1]
    fleet.fence()
    lt, la, _ = fleet.restore_all()
    for t in range(len(SIZES)):
        lo, hi = spec.shard_range(t, 0)          # healthy shard: last save
        np.testing.assert_array_equal(lt[t][lo:hi], (tables[t] + 3)[lo:hi])
        lo, hi = spec.shard_range(t, 1)          # readmitted: reseed full
        np.testing.assert_array_equal(lt[t][lo:hi], (tables[t] + 4)[lo:hi])
    fleet.close()


# ----------------------------------------------- epoch staleness (wire) -----
def test_writer_session_rejects_stale_epoch_commands():
    """Satellite of the failover tentpole, at the wire level: the one
    worker apply loop every transport runs rejects submit/DRAIN/image from
    an epoch older than the one it last adopted — so a superseded
    coordinator cannot apply work or collect a drain ack anywhere."""
    tables, accs = make_state()
    spec = EmbShardSpec(SIZES, 1)
    a, b = socket_mod.socketpair()
    ca, cb = SockChannel(a), SockChannel(b)
    session = WriterSession(0, spec, None, (tables, accs, None), epoch=5)
    t = threading.Thread(target=session.serve, args=(cb, session.gen),
                         daemon=True)
    t.start()
    rows = np.arange(4)
    vals = np.full((4, 8), 2.0, np.float32)
    # stale submit: rejected, never applied
    ca.send(("rows", 4, 1, 0, 0, rows, vals, np.full(4, 2.0, np.float32)))
    assert ca.poll(5.0)
    assert ca.recv() == ("stale", "rows", 4, 5)
    # stale DRAIN: rejected (a stale fence can never collect watermarks)
    ca.send(("drain", 4, 77))
    assert ca.poll(5.0)
    assert ca.recv() == ("stale", "drain", 4, 5)
    # stale image read: rejected too
    ca.send(("image", 4))
    assert ca.poll(5.0)
    assert ca.recv()[:2] == ("stale", "image")
    # current-epoch traffic still works, and the stale submit left no mark
    ca.send(("rows", 5, 1, 0, 0, rows, vals, np.full(4, 2.0, np.float32)))
    assert ca.poll(5.0)
    kind, seq, ev = ca.recv()
    assert kind == "ack" and seq == 1
    ca.send(("drain", 5, 78))
    assert ca.poll(5.0)
    assert ca.recv() == ("drained", 78, 1, None)
    np.testing.assert_array_equal(session.store.image_tables[0][:4], vals)
    ca.send(("close", 5))
    t.join(timeout=5.0)
    assert not t.is_alive()
    ca.close()
    cb.close()


# ------------------------------------- partial-send channel poisoning -------
@pytest.mark.parametrize("codec_level", [0, 6])
def test_partial_send_poisons_channel_and_shard(tmp_path, codec_level):
    """Satellite bugfix: a timeout that interrupts ``sendall`` mid-frame
    leaves the connection desynchronized — it must be severed and never
    reused (reusing it would splice the next frame into the torn one and
    corrupt the stream).  The shard is poisoned; the fleet fences on.
    Parametrized over the wire codec: a tear mid-COMPRESSED-frame severs
    exactly the same way (the inflate state never sees the torn tail)."""
    tables, accs = make_state()
    spec = EmbShardSpec(SIZES, 2)
    opts = ({"codec_level": codec_level, "codec_floor": 64}
            if codec_level else None)
    fleet = ShardedCheckpointWriter(tables, accs, spec,
                                    directory=str(tmp_path),
                                    backend="socket", delta_saves=False,
                                    drain_timeout=15.0,
                                    transport_options=opts)
    fleet.save_full([t + 1 for t in tables], [a + 1 for a in accs], step=1)
    fleet.fence()
    chan = fleet.procs[1]._chan
    if codec_level:     # the stream really is compressed before the tear
        s = chan.wire_stats()
        assert s["wire_sent"] < s["raw_sent"]
    real_sock = chan._sock
    sendall_calls = {"n": 0}

    class ShortWriteSock:
        def __getattr__(self, name):
            return getattr(real_sock, name)

        def sendall(self, data):
            sendall_calls["n"] += 1
            real_sock.send(data[:max(1, len(data) // 2)])   # torn frame
            raise socket_mod.timeout("injected short write")

    chan._sock = ShortWriteSock()
    rows = np.arange(25, 35)                       # owned by shard 1
    fleet.save_rows(0, rows, np.full((10, 8), 7.0, np.float32),
                    np.full(10, 7.0, np.float32), step=2)
    deadline = time.monotonic() + 10.0             # sender thread latches
    while fleet.procs[1].error is None and time.monotonic() < deadline:
        time.sleep(0.02)
    assert fleet.procs[1].error is not None
    assert chan._broken
    n_after_poison = sendall_calls["n"]
    assert n_after_poison == 1
    # the poisoned channel hard-fails instead of splicing another frame
    # after the torn one
    with pytest.raises(BrokenPipeError):
        chan.send(("ping", 1, 99))
    assert sendall_calls["n"] == n_after_poison
    # one torn channel poisons one shard; the fence stamps the other
    with pytest.raises(ShardSaveError) as ei:
        fleet.fence()
    assert sorted(ei.value.shard_errors) == [1]
    fleet.close()
    lt, _, _ = ShardedCheckpointWriter.load_latest(
        str(tmp_path), tables, accs, spec).restore_all()
    for t, n in enumerate(SIZES):
        lo, hi = spec.shard_range(t, 0)
        np.testing.assert_array_equal(lt[t][lo:hi], (tables[t] + 1)[lo:hi])
        lo, hi = spec.shard_range(t, 1)
        np.testing.assert_array_equal(lt[t][lo:hi], (tables[t] + 1)[lo:hi])


# ------------------------------------------ heartbeat/close serialization ---
def test_close_stands_down_heartbeat_monitor(tmp_path):
    """Satellite bugfix (heartbeat/close race): a monitor sweep that fires
    once close() has begun — the workers are mid-shutdown and look dead —
    must be a no-op, not a spurious poison with a ``failed_shards`` entry
    in the final cycle stamp."""
    tables, accs = make_state()
    spec = EmbShardSpec(SIZES, 2)
    fleet = ShardedCheckpointWriter(tables, accs, spec,
                                    directory=str(tmp_path), backend="pipe",
                                    delta_saves=False,
                                    heartbeat_interval=0.02)
    fleet.save_full([t + 1 for t in tables], [a + 1 for a in accs], step=1)
    fleet.close()
    # simulate the racing monitor thread firing late, exactly as if its
    # join had timed out mid-sweep: must not latch the (now gone) workers
    fleet._probe_sweep()
    assert fleet.failed == {}
    assert all(ep.error is None for ep in fleet.endpoints)
    import json
    from repro.core.checkpoint import resolve_run_dir
    run_dir = resolve_run_dir(str(tmp_path))
    with open(os.path.join(run_dir, "manifest.json")) as f:
        cycles = [e for e in json.load(f)["events"] if e["kind"] == "cycle"]
    assert cycles and all(c["failed_shards"] == [] for c in cycles)


def test_clean_close_under_aggressive_heartbeat(tmp_path):
    """Close repeatedly under a monitor probing every few milliseconds:
    the sweep is serialized against the fence/close window, so a clean
    shutdown never records a poisoned shard."""
    tables, accs = make_state()
    spec = EmbShardSpec(SIZES, 2)
    for k in range(3):
        fleet = ShardedCheckpointWriter(
            tables, accs, spec, directory=str(tmp_path / f"r{k}"),
            backend="pipe", delta_saves=False, heartbeat_interval=0.005)
        fleet.save_full([t + 1 for t in tables], [a + 1 for a in accs],
                        step=1)
        fleet.fence()
        fleet.close()
        assert fleet.failed == {}


# --------------------------------------------- monotonic-timer invariant ----
def test_internal_timers_are_monotonic_not_wall_clock():
    """Every internal deadline/back-off timer (heartbeat silence, drain
    deadlines, readmit back-off) must use ``time.monotonic()`` — an NTP
    step must never expire or extend them.  The scan itself lives in the
    analyzer's time-source rule (``repro.analysis``); this is the thin
    tier-1 guard that keeps it green over the whole package."""
    from repro.analysis import run_analysis

    report = run_analysis(rules=["time-source"])
    assert report.unsuppressed == [], "\n".join(
        f.render() for f in report.unsuppressed)


# ---------------------------------------------------- multiplexing ----------
def test_mux_groups_share_servers_and_match_per_conn_fleet(tmp_path):
    """Tentpole: shards multiplexed in groups over shared connections /
    servers must be observably identical to the one-connection-per-shard
    fleet — byte-identical manifests (modulo timestamps) and images for
    the same schedule — while running half the server processes."""
    import json
    from repro.core.checkpoint import resolve_run_dir
    tables, accs = make_state()
    spec = EmbShardSpec(SIZES, 4)
    results = {}
    for label, opts in (("per", None), ("mux", {"mux_group": 2})):
        d = str(tmp_path / label)
        fleet = ShardedCheckpointWriter(
            [t.copy() for t in tables], [a.copy() for a in accs], spec,
            directory=d, backend="socket", delta_saves=False,
            transport_options=opts)
        fleet.save_full([t + 1 for t in tables], [a + 1 for a in accs],
                        step=1)
        fleet.fence()
        rows = np.arange(5)
        fleet.save_rows(0, rows, np.full((5, 8), 9.0, np.float32),
                        np.full(5, 9.0, np.float32), step=2)
        fleet.fence()
        imgs = fleet.restore_all()[:2]
        n_servers = len({ep.pid for ep in fleet.transport.endpoints})
        wire = fleet.wire_stats
        fleet.close()
        with open(os.path.join(resolve_run_dir(d), "manifest.json")) as f:
            results[label] = (imgs, n_servers, wire, json.load(f))
    (p_img, p_servers, p_wire, p_man) = results["per"]
    (m_img, m_servers, m_wire, m_man) = results["mux"]
    for t in range(len(SIZES)):
        np.testing.assert_array_equal(p_img[0][t], m_img[0][t])
        np.testing.assert_array_equal(p_img[1][t], m_img[1][t])
    strip = lambda m: {**m, "events": [
        {k: v for k, v in e.items() if k != "time"} for e in m["events"]]}
    assert strip(p_man) == strip(m_man)
    assert p_servers == 4 and m_servers == 2     # groups of 2 share a server
    # counters live on the shared channels too (mx envelopes add a few
    # bytes per frame, so only rough equality holds vs the per-conn fleet)
    assert m_wire["raw_sent"] > 0 and m_wire["raw_rcvd"] > 0


def test_mux_sever_poisons_exactly_coresident_shards(tmp_path):
    """Severing a multiplexed connection poisons exactly the shards riding
    it — its whole group, and nothing outside it."""
    tables, accs = make_state()
    spec = EmbShardSpec(SIZES, 4)
    fleet = ShardedCheckpointWriter(tables, accs, spec,
                                    directory=str(tmp_path),
                                    backend="socket", delta_saves=False,
                                    drain_timeout=15.0,
                                    transport_options={"mux_group": 2})
    fleet.save_full([t + 1 for t in tables], [a + 1 for a in accs], step=1)
    fleet.fence()
    fleet.procs[0].sever()                # group {0, 1} rides this conn
    fleet.save_full([t + 2 for t in tables], [a + 2 for a in accs], step=2)
    with pytest.raises(ShardSaveError) as ei:
        fleet.fence()
    assert sorted(ei.value.shard_errors) == [0, 1]
    assert 2 not in fleet.failed and 3 not in fleet.failed
    fleet.close()
    lt, _, _ = ShardedCheckpointWriter.load_latest(
        str(tmp_path), tables, accs, spec).restore_all()
    for t in range(len(SIZES)):
        for j, v in ((0, 1), (1, 1), (2, 2), (3, 2)):
            lo, hi = spec.shard_range(t, j)
            np.testing.assert_array_equal(lt[t][lo:hi],
                                          (tables[t] + v)[lo:hi])


def test_mux_kill_takes_down_the_shared_group_server(tmp_path):
    """kill() on a mux member kills the group's shared server process —
    honest group semantics: every co-resident shard poisons, the other
    group stamps on."""
    tables, accs = make_state()
    spec = EmbShardSpec(SIZES, 4)
    fleet = ShardedCheckpointWriter(tables, accs, spec,
                                    directory=str(tmp_path),
                                    backend="socket", delta_saves=False,
                                    drain_timeout=15.0,
                                    transport_options={"mux_group": 2})
    assert fleet.procs[2].pid == fleet.procs[3].pid   # one server per group
    assert fleet.procs[0].pid != fleet.procs[2].pid
    fleet.save_full([t + 1 for t in tables], [a + 1 for a in accs], step=1)
    fleet.fence()
    fleet.kill_shard(2)
    fleet.save_full([t + 2 for t in tables], [a + 2 for a in accs], step=2)
    with pytest.raises(ShardSaveError) as ei:
        fleet.fence()
    assert sorted(set(ei.value.shard_errors) | {2}) == [2, 3]
    assert 0 not in fleet.failed and 1 not in fleet.failed
    fleet.close()


# --------------------------------------------------- socket severance -------
def test_socket_severed_connection_poisons_only_that_shard(tmp_path):
    """A network partition (connection cut, server still running) poisons
    exactly one shard; healthy shards' saves stamp and recovery serves the
    last stamped state."""
    tables, accs = make_state()
    spec = EmbShardSpec(SIZES, 2)
    fleet = ShardedCheckpointWriter(tables, accs, spec,
                                    directory=str(tmp_path),
                                    backend="socket", delta_saves=False,
                                    drain_timeout=15.0)
    fleet.save_full([t + 1 for t in tables], [a + 1 for a in accs], step=1)
    fleet.fence()
    fleet.procs[1].sever()
    fleet.save_full([t + 2 for t in tables], [a + 2 for a in accs], step=2)
    with pytest.raises(ShardSaveError) as ei:
        fleet.fence()
    assert sorted(ei.value.shard_errors) == [1]
    fleet.close()
    lt, _, _ = ShardedCheckpointWriter.load_latest(
        str(tmp_path), tables, accs, spec).restore_all()
    for t, n in enumerate(SIZES):
        lo, hi = spec.shard_range(t, 0)
        np.testing.assert_array_equal(lt[t][lo:hi], (tables[t] + 2)[lo:hi])
        lo, hi = spec.shard_range(t, 1)
        np.testing.assert_array_equal(lt[t][lo:hi], (tables[t] + 1)[lo:hi])


def test_pipe_writer_seed_crosses_as_numpy_only(monkeypatch):
    """A device-array leaf unpickled in a writer child would start a JAX
    backend there, contending for the accelerator the trainer holds: the
    spawn seed (tables, accumulators and trainer image) is numpy only."""
    import multiprocessing as mp

    import jax
    import jax.numpy as jnp

    from repro.core.transport import PipeEndpoint
    spawn = mp.get_context("spawn")
    seen = {}

    class RecordingProcess:
        pid, exitcode = None, 0

        def __init__(self, target, args, name, daemon):
            seen["args"] = args

        def start(self):
            pass

        def is_alive(self):
            return False

        def join(self, timeout=None):
            pass

        def kill(self):
            pass

    class Ctx:
        Pipe = staticmethod(spawn.Pipe)
        Process = RecordingProcess

    monkeypatch.setattr(mp, "get_context", lambda method: Ctx)
    ep = PipeEndpoint(0, EmbShardSpec((8,), 1), [jnp.ones((8, 4))],
                      [jnp.ones((8,))],
                      trainer_image={"w": [jnp.ones((3, 2))],
                                     "b": jnp.zeros(2)})
    ep.close()
    tabs, accs, trainer = seen["args"][4]
    leaves = list(tabs) + list(accs) + jax.tree.leaves(trainer)
    assert len(leaves) == 4
    assert all(type(x) is np.ndarray for x in leaves), \
        [type(x) for x in leaves]
