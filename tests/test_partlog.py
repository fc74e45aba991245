"""Partitioned, replicated event log (ISSUE 9): CRC framing + torn-tail
repair, segment chains, the entity-id partition router, follower
replication with durability-gated acks, SIGKILL crash consistency at
every ``PIO_TPU_DURABILITY`` level, longest-verified-prefix failover,
snapshot compaction (byte-identical to full-history replay, loud
fallbacks), the ``/storage.json`` topology endpoint, breaker shedding
for a dead partition, and the per-reason worker respawn budgets."""

import datetime as dt
import json
import os
import socket
import subprocess
import sys
import textwrap
import time
import urllib.error
import urllib.request

import pytest

from pio_tpu import faults
from pio_tpu.data.datamap import DataMap
from pio_tpu.data.event import Event
from pio_tpu.faults.registry import CRASH_EXIT_CODE, ENV_VAR
from pio_tpu.obs import monotonic_s
from pio_tpu.storage.base import StorageError
from pio_tpu.storage.partlog import (
    PartitionedEventLog, compaction, failover, framing, partition_of,
    replication,
)
from pio_tpu.storage.partlog.segments import SegmentLog


@pytest.fixture(autouse=True)
def clean_faults(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    faults.uninstall()
    yield
    faults.uninstall()


def T(h):
    return dt.datetime(2026, 3, 1, h, tzinfo=dt.timezone.utc)


def ev(name, t, eid="u1", etype="user", target=None, props=None):
    return Event(
        name, etype, eid,
        target_entity_type="item" if target else None,
        target_entity_id=target,
        properties=props or {},
        event_time=t,
    )


# ------------------------------------------------------------------ framing
class TestFraming:
    def test_roundtrip(self, tmp_path):
        data = b"".join(framing.frame(f"p{i}".encode()) for i in range(5))
        payloads, verified, total = framing.scan(data, origin="mem")
        assert payloads == [f"p{i}".encode() for i in range(5)]
        assert verified == total == len(data)

    def test_torn_tail_is_tolerated(self):
        data = framing.frame(b"good") + framing.frame(b"torn")[:-3]
        payloads, verified, total = framing.scan(data, origin="mem")
        assert payloads == [b"good"]
        assert verified == len(framing.frame(b"good")) and total == len(data)

    def test_mid_file_corruption_raises(self):
        a, b = framing.frame(b"aaaa"), framing.frame(b"bbbb")
        bad = bytearray(a + b)
        bad[6] ^= 0xFF  # corrupt frame 0's payload; frame 1 follows whole
        with pytest.raises(StorageError, match="not a torn tail"):
            framing.scan(bytes(bad), origin="mem")

    def test_repair_truncates_loudly(self, tmp_path, caplog):
        p = tmp_path / "seg.log"
        p.write_bytes(framing.frame(b"keep") + b"\x99\x98garbage")
        with caplog.at_level("WARNING", logger="pio_tpu.partlog"):
            dropped = framing.repair(str(p))
        assert dropped == len(b"\x99\x98garbage")
        assert "truncating torn tail" in caplog.text
        assert p.read_bytes() == framing.frame(b"keep")
        assert framing.repair(str(p)) == 0  # already clean: silent no-op

    def test_verified_prefix_of_missing_file(self, tmp_path):
        assert framing.verified_prefix(str(tmp_path / "nope")) == 0


# ----------------------------------------------------------------- segments
class TestSegmentLog:
    def test_append_offsets_and_sealing(self, tmp_path):
        s = SegmentLog(str(tmp_path / "p"), partition=0, seg_bytes=64)
        offs = [s.append(framing.frame(bytes(24))) for _ in range(4)]
        assert offs[0][0] == 0 and all(
            a[1] == b[0] for a, b in zip(offs, offs[1:])
        )
        segs = s.segments()
        assert len(segs) >= 2  # 32-byte frames against a 64-byte roll
        assert [g["start"] for g in segs] == sorted(
            g["start"] for g in segs
        )
        assert sum(g["bytes"] for g in segs) == s.committed
        assert len(s.payloads()) == 4
        s.close()

    def test_read_range_spans_segments(self, tmp_path):
        s = SegmentLog(str(tmp_path / "p"), partition=0, seg_bytes=40)
        whole = b""
        for i in range(6):
            f = framing.frame(f"payload-{i}".encode())
            s.append(f)
            whole += f
        assert s.read_range(0, s.committed) == whole
        assert s.read_range(13, 57) == whole[13:57]
        assert s.read_range(0, 10 ** 9) == whole  # end clamps to committed
        s.close()

    def test_reopen_repairs_torn_tail(self, tmp_path):
        pdir = tmp_path / "p"
        s = SegmentLog(str(pdir), partition=0)
        s.append(framing.frame(b"acked"))
        s.close()
        # simulate a crash mid-append: raw torn bytes past the last frame
        (pdir / "seg-00000001.log").open("ab").write(b"\x07\x00\x00")
        s2 = SegmentLog(str(pdir), partition=0)
        assert s2.payloads() == [b"acked"]
        s2.close()

    def test_injected_torn_write_heals_before_next_append(self, tmp_path):
        s = SegmentLog(str(tmp_path / "p"), partition=0)
        s.append(framing.frame(b"first"))
        faults.install("partlog.append.before_write=torn_write:once")
        with pytest.raises(StorageError, match="torn write"):
            s.append(framing.frame(b"wounded"))
        faults.uninstall()
        # the torn bytes are on disk past committed; the next append
        # must repair them away so the new record scans
        s.append(framing.frame(b"second"))
        assert s.payloads() == [b"first", b"second"]
        s.close()


# ------------------------------------------------------------------ routing
class TestRouter:
    def test_stable_and_spread(self):
        ids = [f"user-{i}" for i in range(200)]
        first = [partition_of(i, 4) for i in ids]
        assert first == [partition_of(i, 4) for i in ids]
        assert set(first) == {0, 1, 2, 3}  # every partition takes load

    def test_same_entity_same_partition(self, tmp_path):
        log = PartitionedEventLog(str(tmp_path / "pl"), partitions=4)
        for h in range(1, 9):
            log.insert(ev("rate", T(h), eid="sticky"), 1)
        k = partition_of("sticky", 4)
        with log._view.lock:
            assert all(
                row[0] == k
                for row in log._view.buckets[(1, None)].values()
            )
        log.close()

    def test_manifest_wins_over_env(self, tmp_path, monkeypatch):
        root = str(tmp_path / "pl")
        PartitionedEventLog(root, partitions=3).close()
        monkeypatch.setenv("PIO_TPU_PARTLOG_PARTITIONS", "8")
        reopened = PartitionedEventLog(root)
        assert reopened.partitions == 3  # repartitioning would strand keys
        reopened.close()

    def test_reopen_replays_view(self, tmp_path):
        root = str(tmp_path / "pl")
        log = PartitionedEventLog(root, partitions=3)
        ids = [
            log.insert(ev("rate", T(h), eid=f"u{h}"), 1)
            for h in range(1, 6)
        ]
        assert log.delete(ids[0], 1)
        log.close()
        again = PartitionedEventLog(root)
        assert {e.event_id for e in again.find(1)} == set(ids[1:])
        again.close()

    def test_post_remove_writes_survive_reopen(self, tmp_path):
        """A channel purge fans one rm record into every partition, but
        replay walks partitions SEQUENTIALLY: each rm must clear only
        its own partition's pre-purge entries, or events acked after
        the purge that routed to a lower-numbered partition get
        replayed first and then wiped by a later partition's rm."""
        root = str(tmp_path / "pl")
        log = PartitionedEventLog(root, partitions=4)
        for h in range(1, 6):
            log.insert(ev("rate", T(h), eid=f"old{h}"), 1)
        assert log.remove(1)
        # new1..new8 spread over all 4 partitions (verified routing)
        ids = [
            log.insert(ev("rate", T(h), eid=f"new{h}"), 1)
            for h in range(1, 9)
        ]
        assert {e.event_id for e in log.find(1)} == set(ids)
        log.close()
        again = PartitionedEventLog(root)
        assert {e.event_id for e in again.find(1)} == set(ids)
        again.close()

    def test_batch_writes_ride_the_committer(self, tmp_path):
        """insert_batch and delete_bulk must go through the partition's
        GroupCommitter (one group payload per partition touched), never
        flush directly — a direct flush could interleave with a
        committer-led flush on the same partition, letting segment
        order and view order diverge."""
        log = PartitionedEventLog(str(tmp_path / "pl"), partitions=2)
        submitted = []
        for k, gc in enumerate(log._committers):
            gc.submit = (
                lambda payload, _k=k, _orig=gc.submit:
                submitted.append((_k, len(payload))) or _orig(payload)
            )
        events = [ev("rate", T(h), eid=f"u{h}") for h in range(1, 9)]
        ids = log.insert_batch(events, 1)
        assert len(ids) == 8
        assert sum(n for _, n in submitted) == 8
        assert {k for k, _ in submitted} == {
            partition_of(f"u{h}", 2) for h in range(1, 9)
        }
        submitted.clear()
        log.delete_bulk(ids[:3], 1)
        assert sum(n for _, n in submitted) == 3
        assert len(log.find(1)) == 5
        log.close()


# -------------------------------------------------------------- replication
class TestReplication:
    def test_follower_mirrors_leader_stream(self, tmp_path, monkeypatch):
        froot = str(tmp_path / "follower")
        f = replication.FollowerServer(froot)
        monkeypatch.setenv(
            "PIO_TPU_PARTLOG_REPLICAS", f"127.0.0.1:{f.port}"
        )
        monkeypatch.setenv("PIO_TPU_DURABILITY", "commit")
        log = PartitionedEventLog(str(tmp_path / "leader"), partitions=2)
        for h in range(1, 7):
            log.insert(ev("rate", T(h), eid=f"u{h}"), 1)
        # commit durability: insert returned ⇒ the follower acked, so
        # its mirror must already hold every partition's full stream
        for k in range(2):
            mirror = os.path.join(froot, f"p{k:03d}.repl")
            want = log.read_range(k, 0, log.committed(k))
            assert framing.verified_prefix(mirror) == len(want)
            with open(mirror, "rb") as fh:
                assert fh.read(len(want)) == want
        log.close()
        f.stop()

    def test_ack_timeout_fails_fast(self, tmp_path, monkeypatch):
        # a replica address nobody answers: commit-durability inserts
        # must fail with a NON-transient error (fast path to the
        # breaker), not burn the retry budget
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
        s.close()
        monkeypatch.setenv(
            "PIO_TPU_PARTLOG_REPLICAS", f"127.0.0.1:{dead_port}"
        )
        monkeypatch.setenv("PIO_TPU_REPL_ACK_TIMEOUT_S", "0.2")
        monkeypatch.setenv("PIO_TPU_REPL_CONNECT_DEADLINE_S", "0.2")
        monkeypatch.setenv("PIO_TPU_DURABILITY", "commit")
        log = PartitionedEventLog(str(tmp_path / "leader"), partitions=2)
        from pio_tpu.storage.retry import is_transient

        t0 = monotonic_s()
        with pytest.raises(StorageError, match="replication ack timeout") as ei:
            log.insert(ev("rate", T(1)), 1)
        assert not is_transient(ei.value)
        assert monotonic_s() - t0 < 5.0
        log.close()

    def test_ack_timeout_does_not_duplicate_appends(
        self, tmp_path, monkeypatch
    ):
        """An ack timeout fires AFTER the blob hit the leader's segment
        log: the flush must report it via PartialFlushOutcome so the
        committer fails the whole batch in ONE timeout — a generic
        raise would trigger the solo-retry path, re-appending every
        already-persisted payload and waiting the timeout per payload."""
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
        s.close()
        monkeypatch.setenv(
            "PIO_TPU_PARTLOG_REPLICAS", f"127.0.0.1:{dead_port}"
        )
        monkeypatch.setenv("PIO_TPU_REPL_ACK_TIMEOUT_S", "0.2")
        monkeypatch.setenv("PIO_TPU_REPL_CONNECT_DEADLINE_S", "0.2")
        monkeypatch.setenv("PIO_TPU_DURABILITY", "commit")
        log = PartitionedEventLog(str(tmp_path / "leader"), partitions=1)
        events = [ev("rate", T(h), eid=f"u{h}") for h in range(1, 7)]
        t0 = monotonic_s()
        with pytest.raises(StorageError, match="replication ack timeout"):
            log.insert_batch(events, 1)
        # one timeout for the whole batch, not (B+1) solo re-waits
        assert monotonic_s() - t0 < 2.0
        # each record persisted exactly once — no solo re-appends
        assert len(log._segs[0].payloads()) == 6
        # persisted-but-unacked: live view matches what replay serves
        assert len(log.find(1)) == 6
        log.close()
        again = PartitionedEventLog(str(tmp_path / "leader"))
        assert len(again.find(1)) == 6
        again.close()

    def test_min_acks_above_replica_count_raises(
        self, tmp_path, monkeypatch
    ):
        # silently capping min_acks to the replica count would weaken
        # the durability guarantee the operator asked for — misconfig
        # must fail construction loudly (durability.mode() policy)
        monkeypatch.setenv("PIO_TPU_PARTLOG_REPLICAS", "127.0.0.1:9")
        monkeypatch.setenv("PIO_TPU_REPL_MIN_ACKS", "3")
        with pytest.raises(StorageError, match="PIO_TPU_REPL_MIN_ACKS"):
            PartitionedEventLog(str(tmp_path / "leader"), partitions=2)

    def test_reconnect_catches_up(self, tmp_path, monkeypatch):
        """A follower that was down during the writes reconnects and
        pulls the whole backlog (jittered-deadline reconnect path)."""
        froot = str(tmp_path / "follower")
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
        probe.close()  # reserve then release: follower starts here LATER
        monkeypatch.setenv("PIO_TPU_PARTLOG_REPLICAS", f"127.0.0.1:{port}")
        monkeypatch.setenv("PIO_TPU_DURABILITY", "batch")  # no ack gate
        monkeypatch.setenv("PIO_TPU_REPL_CONNECT_DEADLINE_S", "15")
        log = PartitionedEventLog(str(tmp_path / "leader"), partitions=2)
        for h in range(1, 7):
            log.insert(ev("rate", T(h), eid=f"u{h}"), 1)
        f = replication.FollowerServer(
            froot, port=port
        )  # comes up late; the link's retrying() reconnect finds it
        want = {k: log.committed(k) for k in range(2)}
        deadline = monotonic_s() + 20
        while monotonic_s() < deadline:
            got = {
                k: framing.verified_prefix(
                    os.path.join(froot, f"p{k:03d}.repl")
                )
                for k in range(2)
            }
            if got == want:
                break
            time.sleep(0.05)
        assert got == want, f"follower never caught up: {got} != {want}"
        log.close()
        f.stop()


# --------------------------------------------- crash consistency + failover
_CRASH_WRITER = textwrap.dedent("""
    import datetime as dt
    import os
    import sys

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    root, ackfile = sys.argv[1], sys.argv[2]

    from pio_tpu.data.event import Event
    from pio_tpu.storage.partlog import PartitionedEventLog

    b = PartitionedEventLog(root)
    t = dt.datetime(2026, 3, 1, tzinfo=dt.timezone.utc)
    ack = open(ackfile, "w")
    for i in range(12):
        eid = b.insert(
            Event(event="e", entity_type="u", entity_id=f"u{i}",
                  event_time=t),
            1,
        )
        # the ack protocol: an id reaches this file only AFTER insert
        # returned (the 201 analog), fsynced so the parent can trust it
        ack.write(eid + "\\n")
        ack.flush()
        os.fsync(ack.fileno())

    from pio_tpu import faults
    faults.install("groupcommit.flush.partlog*=crash:once")
    b.insert(
        Event(event="e", entity_type="u", entity_id="boom", event_time=t),
        1,
    )
    print("UNREACHABLE")
""")


def _run_writer(script, *argv, env_extra=None):
    env = dict(os.environ)
    env.pop(ENV_VAR, None)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-c", script, *argv],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )


class TestCrashFailover:
    @pytest.mark.parametrize("level", ["commit", "batch", "os"])
    def test_sigkill_leader_mid_commit_with_two_followers(
        self, tmp_path, level
    ):
        """The chaos drill, per durability level: the leader process
        dies (os._exit, no unwinding) inside a partition group-commit
        flush with two live followers. A follower with the longest
        verified prefix is promoted; at ``commit`` durability the
        promoted log must serve EVERY acked write (the ack was gated on
        follower fsync); at every level the promoted root opens clean
        and keeps accepting writes."""
        froot1 = str(tmp_path / "f1")
        froot2 = str(tmp_path / "f2")
        f1 = replication.FollowerServer(froot1)
        f2 = replication.FollowerServer(froot2)
        root = str(tmp_path / "leader")
        ackfile = str(tmp_path / "acks")
        try:
            proc = _run_writer(
                _CRASH_WRITER, root, ackfile,
                env_extra={
                    "PIO_TPU_DURABILITY": level,
                    "PIO_TPU_PARTLOG_PARTITIONS": "3",
                    "PIO_TPU_PARTLOG_REPLICAS":
                        f"127.0.0.1:{f1.port},127.0.0.1:{f2.port}",
                },
            )
        finally:
            f1.stop()
            f2.stop()
        assert proc.returncode == CRASH_EXIT_CODE, proc.stderr
        assert "injected crash" in proc.stderr
        assert "UNREACHABLE" not in proc.stdout
        with open(ackfile) as f:
            acked = [line.strip() for line in f if line.strip()]
        assert len(acked) == 12

        dest = str(tmp_path / "promoted")
        res = failover.promote([froot1, froot2], dest)
        assert res["partitions"] == 3
        b = PartitionedEventLog(dest)
        got = {e.event_id for e in b.find(1)}
        if level == "commit":
            assert set(acked) <= got, (
                f"lost acked events: {set(acked) - got}"
            )
            assert "boom" not in {e.entity_id for e in b.find(1)}
        # at every level the promoted log recovered clean and serves
        n = len(b.find(1))
        b.insert(ev("e", T(9), eid="after-failover"), 1)
        assert len(b.find(1)) == n + 1
        b.close()


class TestElection:
    def _mk_follower_root(self, path, streams, torn=b""):
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "MANIFEST.json"), "w") as f:
            json.dump({"version": 1, "partitions": len(streams)}, f)
        for k, payloads in enumerate(streams):
            with open(os.path.join(path, f"p{k:03d}.repl"), "wb") as f:
                for p in payloads:
                    f.write(framing.frame(p))
                f.write(torn)

    def test_longest_verified_prefix_wins_per_partition(self, tmp_path):
        a, b = str(tmp_path / "a"), str(tmp_path / "b")
        # a leads on partition 0; b leads on partition 1 — election is
        # PER PARTITION, so each winner is chosen independently
        self._mk_follower_root(a, [[b"x", b"y"], [b"q"]])
        self._mk_follower_root(b, [[b"x"], [b"q", b"r", b"s"]])
        out = failover.elect([a, b])
        assert out[0]["winner"] == a
        assert out[1]["winner"] == b
        assert out[0]["position"] == len(framing.frame(b"x") * 2)
        assert set(out[0]["candidates"]) == {a, b}

    def test_torn_tail_never_scores_and_promote_drops_it(
        self, tmp_path, caplog
    ):
        a, b = str(tmp_path / "a"), str(tmp_path / "b")
        # b has MORE raw bytes but its tail is torn — a's fully-verified
        # stream must win, and promotion from a torn winner truncates
        self._mk_follower_root(a, [[b"x", b"y"]])
        self._mk_follower_root(b, [[b"x"]], torn=framing.frame(b"t")[:-2])
        out = failover.elect([a, b])
        assert out[0]["winner"] == a
        dest = str(tmp_path / "dest")
        with caplog.at_level("WARNING", logger="pio_tpu.partlog"):
            failover.promote([b], dest)  # only the torn candidate left
        assert "torn bytes" in caplog.text
        seg = os.path.join(dest, "p000", "seg-00000001.log")
        assert open(seg, "rb").read() == framing.frame(b"x")

    def test_no_manifest_anywhere_raises(self, tmp_path):
        with pytest.raises(StorageError, match="MANIFEST"):
            failover.elect([str(tmp_path / "empty")])

    def test_promote_refuses_nonempty_dest(self, tmp_path):
        # a prior incarnation's files (an older seg-00000002.log, a
        # snapshot) would mix into the promoted chain — refuse loudly
        a = str(tmp_path / "a")
        self._mk_follower_root(a, [[b"x"]])
        dest = str(tmp_path / "dest")
        os.makedirs(os.path.join(dest, "p000"))
        with open(
            os.path.join(dest, "p000", "seg-00000002.log"), "wb"
        ) as f:
            f.write(framing.frame(b"stale"))
        with pytest.raises(StorageError, match="not empty"):
            failover.promote([a], dest)
        # a pre-created but EMPTY dest is fine
        dest2 = str(tmp_path / "dest2")
        os.makedirs(dest2)
        res = failover.promote([a], dest2)
        assert res["partitions"] == 1


# --------------------------------------------------------------- compaction
class TestCompaction:
    def _fill(self, log):
        log.insert(ev("$set", T(1), "u1", props={"a": 1, "plan": "free"}), 1)
        log.insert(ev("$set", T(2), "u1", props={"plan": "pro"}), 1)
        log.insert(ev("$unset", T(3), "u1", props={"a": None}), 1)
        log.insert(ev("$set", T(1), "u2", props={"b": 2}), 1)
        log.insert(ev("$delete", T(2), "u2"), 1)
        log.insert(ev("$set", T(1), "u3", props={"c": 3}), 1)
        log.insert(ev("rate", T(4), "u1", target="i1"), 1)

    @staticmethod
    def _dump(agg):
        return {
            k: (v.to_dict(), v.first_updated, v.last_updated)
            for k, v in sorted(agg.items())
        }

    def test_snapshot_read_identical_to_full_replay(self, tmp_path):
        log = PartitionedEventLog(str(tmp_path / "pl"), partitions=3)
        self._fill(log)
        before = log.aggregate_properties(1, "user")
        log.compact()
        topo = log.topology()
        assert all(
            p["snapshot_watermark"] == p["records"]
            for p in topo["partition_detail"] if p["records"]
        )
        after = log.aggregate_properties(1, "user")
        assert self._dump(before) == self._dump(after)
        # cold reopen reads the snapshot from disk, same answer
        log.close()
        again = PartitionedEventLog(str(tmp_path / "pl"))
        assert self._dump(again.aggregate_properties(1, "user")) == \
            self._dump(before)
        again.close()

    def test_resume_fold_past_watermark(self, tmp_path):
        log = PartitionedEventLog(str(tmp_path / "pl"), partitions=3)
        self._fill(log)
        log.compact()
        log.insert(ev("$set", T(5), "u1", props={"tier": "gold"}), 1)
        log.insert(ev("$set", T(5), "u9", props={"new": True}), 1)
        agg = log.aggregate_properties(1, "user")
        assert agg["u1"].to_dict() == {"plan": "pro", "tier": "gold"}
        assert agg["u9"].to_dict() == {"new": True}  # born post-watermark
        log.close()

    def test_checksum_fallback_is_loud_and_exact(self, tmp_path, caplog):
        log = PartitionedEventLog(str(tmp_path / "pl"), partitions=2)
        self._fill(log)
        want = self._dump(log.aggregate_properties(1, "user"))
        log.compact()
        fell = compaction._FALLBACKS.value("checksum")
        # flip a byte inside every partition's snapshot body
        for k in range(2):
            p = os.path.join(
                str(tmp_path / "pl"), f"p{k:03d}", "snapshot.json"
            )
            raw = bytearray(open(p, "rb").read())
            raw[len(raw) // 2] ^= 0xFF
            open(p, "wb").write(bytes(raw))
        log._snapshots.clear()
        with caplog.at_level("WARNING", logger="pio_tpu.partlog"):
            got = self._dump(log.aggregate_properties(1, "user"))
        assert got == want  # fallback is the exact full fold
        assert "sha256" in caplog.text
        assert compaction._FALLBACKS.value("checksum") > fell
        log.close()

    def test_rewritten_history_falls_back(self, tmp_path):
        log = PartitionedEventLog(str(tmp_path / "pl"), partitions=2)
        self._fill(log)
        log.compact()
        # delete a PRE-watermark $set: the snapshot's folded state for
        # u1 is now stale and its event count no longer matches
        doomed = [
            e for e in log.find(1, entity_id="u1", event_names=["$set"])
            if e.properties.get("plan") == "pro"
        ]
        assert log.delete(doomed[0].event_id, 1)
        fell = compaction._FALLBACKS.value("history_rewritten")
        agg = log.aggregate_properties(1, "user")
        assert agg["u1"].to_dict() == {"plan": "free"}  # re-folded truth
        assert compaction._FALLBACKS.value("history_rewritten") > fell
        log.close()

    def test_out_of_order_suffix_falls_back(self, tmp_path):
        log = PartitionedEventLog(str(tmp_path / "pl"), partitions=2)
        log.insert(ev("$set", T(5), "u1", props={"plan": "pro"}), 1)
        log.compact()
        # a suffix event OLDER than the folded max: resuming would fold
        # it after the snapshot state — the exact order folds it before
        log.insert(ev("$set", T(2), "u1", props={"plan": "free"}), 1)
        fell = compaction._FALLBACKS.value("out_of_order")
        agg = log.aggregate_properties(1, "user")
        assert agg["u1"].to_dict() == {"plan": "pro"}  # T(5) still wins
        assert compaction._FALLBACKS.value("out_of_order") > fell
        log.close()

    def test_time_windowed_reads_bypass_snapshot(self, tmp_path):
        log = PartitionedEventLog(str(tmp_path / "pl"), partitions=2)
        self._fill(log)
        log.compact()
        agg = log.aggregate_properties(1, "user", until_time=T(2))
        assert agg["u1"].to_dict() == {"a": 1, "plan": "free"}
        log.close()


# -------------------------------------------------- /storage.json + breaker
def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _http(method, url, body=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    req.add_header("Content-Type", "application/json")
    try:
        with urllib.request.urlopen(req, timeout=15) as resp:
            return (resp.status, json.loads(resp.read() or b"null"),
                    {k.lower(): v for k, v in resp.headers.items()})
    except urllib.error.HTTPError as e:
        return (e.code, json.loads(e.read() or b"null"),
                {k.lower(): v for k, v in e.headers.items()})


@pytest.fixture()
def partlog_server_env(tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_TPU_HOME", str(tmp_path))
    monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_METADATA_SOURCE", "MEM")
    monkeypatch.setenv("PIO_STORAGE_SOURCES_MEM_TYPE", "memory")
    monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE", "MEM")
    monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE", "PL")
    monkeypatch.setenv("PIO_STORAGE_SOURCES_PL_TYPE", "partlog")
    monkeypatch.setenv(
        "PIO_STORAGE_SOURCES_PL_PATH", str(tmp_path / "partlog")
    )
    monkeypatch.setenv("PIO_TPU_PARTLOG_PARTITIONS", "3")
    from pio_tpu.storage import Storage

    Storage.reset()
    yield monkeypatch
    Storage.reset()


class TestStorageEndpoint:
    def test_partlog_topology(self, partlog_server_env):
        from pio_tpu.server import create_event_server
        from pio_tpu.storage import AccessKey, App, Storage

        app_id = Storage.get_meta_data_apps().insert(App(0, "topo"))
        key = Storage.get_meta_data_access_keys().insert(
            AccessKey("", app_id)
        )
        server = create_event_server(host="127.0.0.1", port=0).start()
        try:
            url = f"http://127.0.0.1:{server.port}"
            e = {"event": "rate", "entityType": "user", "entityId": "u1",
                 "eventTime": "2026-03-01T10:00:00Z"}
            assert _http(
                "POST", f"{url}/events.json?accessKey={key}", e
            )[0] == 201
            status, topo, _ = _http("GET", f"{url}/storage.json")
            assert status == 200
            assert topo["backend"] == "partlog"
            assert topo["role"] == "leader" and topo["partitions"] == 3
            assert len(topo["partition_detail"]) == 3
            assert sum(
                p["records"] for p in topo["partition_detail"]
            ) == 1
            assert topo["replication"] is None  # no replicas configured
        finally:
            server.stop()

    def test_non_partlog_backend_reports_type(self, tmp_home, monkeypatch):
        from pio_tpu.server import create_event_server
        from pio_tpu.storage import Storage

        monkeypatch.setenv(
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE", "MEM"
        )
        monkeypatch.setenv("PIO_STORAGE_SOURCES_MEM_TYPE", "memory")
        monkeypatch.setenv(
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE", "MEM"
        )
        Storage.reset()
        server = create_event_server(host="127.0.0.1", port=0).start()
        try:
            status, body, _ = _http(
                "GET", f"http://127.0.0.1:{server.port}/storage.json"
            )
            assert status == 200
            assert body == {"backend": "MemLEvents", "topology": None}
        finally:
            server.stop()
            Storage.reset()


class TestBreakerShedsDeadPartition:
    def test_dead_replica_opens_breaker_503(self, partlog_server_env):
        """Satellite 2: commit-durability inserts against a replica
        that never acks fail fast (non-transient ack timeout), trip the
        storage breaker, and subsequent writes shed 503 + Retry-After
        with the shed counted against the SLO budget."""
        mp = partlog_server_env
        mp.setenv(
            "PIO_TPU_PARTLOG_REPLICAS", f"127.0.0.1:{_free_port()}"
        )
        mp.setenv("PIO_TPU_REPL_ACK_TIMEOUT_S", "0.2")
        mp.setenv("PIO_TPU_REPL_CONNECT_DEADLINE_S", "0.2")
        mp.setenv("PIO_TPU_DURABILITY", "commit")
        from pio_tpu.server import create_event_server
        from pio_tpu.storage import AccessKey, App, Storage

        Storage.reset()
        app_id = Storage.get_meta_data_apps().insert(App(0, "breaker"))
        key = Storage.get_meta_data_access_keys().insert(
            AccessKey("", app_id)
        )
        server = create_event_server(
            host="127.0.0.1", port=0,
            qos="rps=1000,fail_rate=0.5,fail_window=4,"
                "cooldown=60s,probes=1",
        ).start()
        try:
            url = f"http://127.0.0.1:{server.port}"
            e = {"event": "rate", "entityType": "user", "entityId": "u1",
                 "eventTime": "2026-03-01T10:00:00Z"}
            for _ in range(4):
                status, _, _ = _http(
                    "POST", f"{url}/events.json?accessKey={key}", e
                )
                assert status == 500  # ack timeout surfaces, not hangs
            # breaker open: fail fast BEFORE storage is touched again
            status, body, headers = _http(
                "POST", f"{url}/events.json?accessKey={key}", e
            )
            assert status == 503
            assert "breaker" in body["message"]
            assert int(headers["retry-after"]) >= 1
            snap = _http("GET", f"{url}/qos.json")[1]
            assert snap["breakers"]["storage"]["state"] == "open"
            assert snap["shed"]["breaker"] >= 1
        finally:
            server.stop()


# ------------------------------------------- worker pool per-reason budgets
class TestRespawnBudgetSplit:
    def _shell(self, n=1, owns_device=False):
        import threading

        from pio_tpu.obs import REGISTRY
        from pio_tpu.server.worker_pool import (
            _MAX_RESPAWNS_BY_REASON, ServingPool,
        )

        pool = ServingPool.__new__(ServingPool)  # no spawn
        pool.n_workers = n
        pool._owns_device = owns_device
        pool._shutdown = threading.Event()
        pool._respawns = [
            {r: 0 for r in _MAX_RESPAWNS_BY_REASON} for _ in range(n)
        ]
        pool._retired = [False] * n
        pool._respawn_due = [0.0] * n
        pool._spawned_at = [0.0] * n
        pool._kill_reason = [None] * n
        pool._respawn_counter = REGISTRY.counter(
            "pio_tpu_worker_respawn_total", "", ("reason",)
        )
        return pool

    def test_unhealthy_kills_do_not_burn_crash_budget(self):
        from pio_tpu.server.worker_pool import _MAX_RESPAWNS_BY_REASON

        pool = self._shell()
        for _ in range(_MAX_RESPAWNS_BY_REASON["unhealthy"]):
            pool._kill_reason[0] = "unhealthy"
            pool._account_death(0, -9, now=100.0)
            assert pool._respawn_due[0] > 0.0
            pool._respawn_due[0] = 0.0
        assert pool._respawns[0]["crash"] == 0
        assert not pool._retired[0]
        # the crash budget is untouched: a real crash still respawns
        pool._account_death(0, 1, now=100.0)
        assert pool._respawns[0]["crash"] == 1
        assert pool._respawn_due[0] > 0.0

    def test_each_reason_retires_on_its_own_budget(self):
        from pio_tpu.server.worker_pool import _MAX_RESPAWNS_BY_REASON

        pool = self._shell()
        for _ in range(_MAX_RESPAWNS_BY_REASON["crash"]):
            pool._account_death(0, 1, now=50.0)
            pool._respawn_due[0] = 0.0
        assert not pool._retired[0]
        pool._account_death(0, 1, now=50.0)  # budget spent: retire
        assert pool._retired[0]
        assert pool._respawn_due[0] == 0.0
        # retired is terminal — even an unhealthy death stays down
        pool._kill_reason[0] = "unhealthy"
        pool._account_death(0, -9, now=50.0)
        assert pool._respawn_due[0] == 0.0

    def test_device_owner_retirement_stops_pool(self):
        """The siblings of a device-owning worker 0 are pinned to the
        host mirror: once it is retired the pool must stop, not keep
        answering from the CPU. A sibling's retirement (or worker 0's in
        a plain pool) leaves the pool up."""
        from pio_tpu.server.worker_pool import _MAX_RESPAWNS_BY_REASON

        spent = _MAX_RESPAWNS_BY_REASON["crash"] + 1
        for owns, idx, stops in (
            (True, 0, True), (True, 1, False), (False, 0, False),
        ):
            pool = self._shell(n=2, owns_device=owns)
            for _ in range(spent):
                pool._account_death(idx, 1, now=50.0)
                pool._respawn_due[idx] = 0.0
            assert pool._retired[idx]
            assert pool._shutdown.is_set() is stops, (owns, idx)

    def test_long_uptime_resets_every_reason(self):
        pool = self._shell()
        pool._kill_reason[0] = "unhealthy"
        pool._account_death(0, -9, now=10.0)
        pool._account_death(0, 1, now=10.0)
        assert pool._respawns[0] == {"crash": 1, "unhealthy": 1}
        pool._respawn_due[0] = 0.0
        pool._spawned_at[0] = 10.0
        pool._account_death(0, 1, now=10.0 + 61.0)  # served 61s: not a loop
        assert pool._respawns[0] == {"crash": 1, "unhealthy": 0}

    def test_backoff_tracks_per_reason_streak(self):
        from pio_tpu.server.worker_pool import _RESPAWN_BACKOFF_BASE_S

        pool = self._shell()
        pool._account_death(0, 1, now=100.0)
        pool._respawn_due[0] = 0.0
        pool._account_death(0, 1, now=100.0)
        crash_delay_2 = pool._respawn_due[0] - 100.0
        assert crash_delay_2 == pytest.approx(_RESPAWN_BACKOFF_BASE_S * 2)
        pool._respawn_due[0] = 0.0
        # first unhealthy death: ITS streak is 1 → base delay, not the
        # doubled cool-down the crash streak earned
        pool._kill_reason[0] = "unhealthy"
        pool._account_death(0, -9, now=100.0)
        assert pool._respawn_due[0] - 100.0 == pytest.approx(
            _RESPAWN_BACKOFF_BASE_S
        )
