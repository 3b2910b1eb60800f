"""Durable-truth hardening (docs/durability.md): the checksummed,
hash-chained journal envelope, torn-tail salvage vs interior quarantine,
the AI4E_TASKSTORE_FSYNC policy ladder, the disk-fault degraded mode, and
checksum-verified replication.

The headline regressions:

- a torn final journal line (kill mid-append) used to CRASH-LOOP the
  store at boot (bare ``json.loads``), and even a skip-only fix would
  leave the ``"a"``-mode handle concatenating the next record onto the
  torn tail — salvage truncates BEFORE the handle opens;
- ``_append``'s old "already made this mutation durable" claim was false
  for a machine crash — the fsync policy ladder makes the real contract
  explicit and testable;
- a checksum-failing replicated line used to absorb silently — now it
  forces the follower's generation-mismatch resync path.
"""

import asyncio
import errno
import json
import os
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from ai4e_tpu.chaos.disk import DiskFaultInjector, attach_journal_faults
from ai4e_tpu.metrics import MetricsRegistry
from ai4e_tpu.taskstore import (APITask, FollowerTaskStore,
                                JournalCorruptError, JournalDegradedError,
                                JournaledTaskStore, TaskNotFound, TaskStatus)
from ai4e_tpu.taskstore import journal as jf
from ai4e_tpu.taskstore.http import make_app
from ai4e_tpu.taskstore.replication import (JournalReplicator,
                                            split_complete_lines)


def run(coro):
    return asyncio.run(coro)


async def serve(app):
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


async def wait_for(predicate, timeout=10.0, interval=0.02):
    deadline = asyncio.get_event_loop().time() + timeout
    while asyncio.get_event_loop().time() < deadline:
        if predicate():
            return True
        await asyncio.sleep(interval)
    return predicate()


def store_at(tmp_path, name="j", **kw):
    kw.setdefault("metrics", MetricsRegistry())
    return JournaledTaskStore(str(tmp_path / name), **kw)


def make_task(body=b"payload", endpoint="/v1/dur/x"):
    return APITask(endpoint=endpoint, body=body, status="created",
                   publish=False)


# -- envelope + chain math ---------------------------------------------------


class TestEnvelope:
    def test_crc32c_known_vectors(self):
        # RFC 3720 appendix test vector + the empty string.
        assert jf.crc32c(b"123456789") == 0xE3069283
        assert jf.crc32c(b"") == 0
        assert jf.crc32c(b"\x00" * 32) == 0x8A9136AA

    def test_encode_verify_round_trip_and_chain(self):
        line1, c1 = jf.encode_record({"a": 1}, jf.GENESIS)
        line2, c2 = jf.encode_record({"b": 2}, c1)
        rec1, got1, legacy1 = jf.verify_line(line1, jf.GENESIS)
        rec2, got2, legacy2 = jf.verify_line(line2, got1)
        assert (rec1, rec2) == ({"a": 1}, {"b": 2})
        assert (got1, got2) == (c1, c2)
        assert not legacy1 and not legacy2

    def test_bit_flip_detected_at_the_exact_record(self):
        line, _ = jf.encode_record({"a": 1}, jf.GENESIS)
        tampered = line[:-2] + ("9" if line[-2] != "9" else "8") + line[-1]
        with pytest.raises(JournalCorruptError) as exc:
            jf.verify_line(tampered, jf.GENESIS)
        assert exc.value.reason == "checksum"

    def test_dropped_predecessor_breaks_the_chain(self):
        line1, c1 = jf.encode_record({"a": 1}, jf.GENESIS)
        line2, _ = jf.encode_record({"b": 2}, c1)
        # Verify line2 as if line1 never existed: its own checksum is
        # fine, the CHAIN is what catches the fork.
        with pytest.raises(JournalCorruptError) as exc:
            jf.verify_line(line2, jf.GENESIS)
        assert exc.value.reason == "chain"

    def test_legacy_line_verifies_and_advances_the_chain(self):
        rec, chain, legacy = jf.verify_line('{"Epoch": 3}', jf.GENESIS)
        assert legacy and rec == {"Epoch": 3}
        assert chain != jf.GENESIS  # the head stays well-defined
        # Unanchored legacy (prev unknown) stays unanchored.
        _, chain2, _ = jf.verify_line('{"Epoch": 3}', None)
        assert chain2 is None

    def test_malformed_envelope_is_corrupt(self):
        with pytest.raises(JournalCorruptError):
            jf.verify_line("J1:zzzzzzzz:00000000:{}", jf.GENESIS)
        with pytest.raises(JournalCorruptError):
            jf.verify_line("not json at all", jf.GENESIS)

    def test_fsync_policy_grammar(self):
        assert jf.parse_fsync_policy("never") == ("never", 0.0)
        assert jf.parse_fsync_policy("always") == ("always", 0.0)
        kind, s = jf.parse_fsync_policy("group:20")
        assert kind == "group" and abs(s - 0.02) < 1e-9
        # NaN/inf windows would construct a store whose group fsync
        # silently never fires (NaN compares False both ways) — the
        # validator must refuse them like any other junk (review
        # finding).
        for bad in ("sometimes", "group:", "group:-5", "group:x",
                    "group:nan", "group:inf", "group:-inf", "group:0"):
            with pytest.raises(ValueError):
                jf.parse_fsync_policy(bad)


# -- split_complete_lines edge cases (replication's shared split rule) -------


class TestSplitCompleteLines:
    def test_empty_buffer(self):
        assert split_complete_lines(b"") == ([], b"")

    def test_crlf_terminated_records(self):
        lines, rest = split_complete_lines(b"alpha\r\nbeta\r\n")
        assert lines == ["alpha", "beta"]
        assert rest == b""

    def test_record_straddling_three_chunks(self):
        record = b'{"TaskId": "abc", "Status": "created"}\n'
        chunks = [record[:10], record[10:25], record[25:]]
        buffer = b""
        collected = []
        for chunk in chunks:
            lines, buffer = split_complete_lines(buffer + chunk)
            collected.extend(lines)
        assert collected == [record.decode().rstrip("\n")]
        assert buffer == b""

    def test_final_chunk_with_no_newline_stays_buffered(self):
        lines, rest = split_complete_lines(b"done\npart")
        assert lines == ["done"]
        assert rest == b"part"  # absorbed whole or not at all


# -- salvage vs quarantine ---------------------------------------------------


class TestSalvage:
    def test_kill_mid_append_boot_clean_then_append_parses(self, tmp_path):
        """THE regression: torn final line → boot clean (no crash-loop),
        truncated before the append handle opens, and a post-boot append
        lands on a clean boundary (parses + survives another restart)."""
        s = store_at(tmp_path)
        kept = s.upsert(make_task())
        s.close()
        path = str(tmp_path / "j")
        with open(path, "a", encoding="utf-8") as fh:
            fh.write('J1:12345678:9abcdef0:{"TaskId": "torn-mid-wri')
        s2 = store_at(tmp_path)  # must not raise
        assert s2.get(kept.task_id).canonical_status == "created"
        with pytest.raises(TaskNotFound):
            s2.get("torn-mid-wri")
        after = s2.upsert(make_task(body=b"post-salvage"))
        s2.close()
        # Every line of the final file parses — the torn tail was
        # truncated, never concatenated onto.
        scan = jf.scan_journal(path)
        assert scan.clean
        s3 = store_at(tmp_path)
        assert s3.get(after.task_id).canonical_status == "created"
        s3.close()

    def test_salvage_writes_report_sidecar_and_metric(self, tmp_path):
        metrics = MetricsRegistry()
        s = store_at(tmp_path, metrics=metrics)
        s.upsert(make_task())
        s.close()
        path = str(tmp_path / "j")
        with open(path, "a", encoding="utf-8") as fh:
            fh.write("garbage-tail-no-newline")
        metrics2 = MetricsRegistry()
        s2 = JournaledTaskStore(path, metrics=metrics2)
        s2.close()
        report = json.load(open(path + ".salvage.json"))
        assert report["dropped_bytes"] == len("garbage-tail-no-newline")
        assert report["records_kept"] == 1
        assert metrics2.counter(
            "ai4e_journal_salvages_total", "").value(reason="torn") == 1
        assert s2.journal_stats()["salvages"] == 1

    def test_complete_but_corrupt_final_line_is_salvaged(self, tmp_path):
        s = store_at(tmp_path)
        kept = s.upsert(make_task())
        doomed = s.upsert(make_task(body=b"doomed"))
        s.close()
        path = str(tmp_path / "j")
        lines = open(path).read().splitlines()
        lines[-1] = lines[-1][:-3] + 'xx}'  # newline-terminated, bad CRC
        open(path, "w").write("\n".join(lines) + "\n")
        s2 = store_at(tmp_path)
        assert s2.get(kept.task_id)
        with pytest.raises(TaskNotFound):
            s2.get(doomed.task_id)
        s2.close()

    def test_legacy_checksumless_journal_torn_tail_salvaged(self, tmp_path):
        path = str(tmp_path / "legacy")
        with open(path, "w", encoding="utf-8") as fh:
            fh.write(json.dumps({"TaskId": "old-1", "Endpoint": "/v1/x",
                                 "Status": "created",
                                 "BackendStatus": "created"}) + "\n")
            fh.write('{"TaskId": "old-torn", "Endp')  # kill mid-append
        s = JournaledTaskStore(path, metrics=MetricsRegistry())
        assert s.get("old-1").canonical_status == "created"
        with pytest.raises(TaskNotFound):
            s.get("old-torn")
        s.close()

    def test_corrupt_interior_record_refuses_loudly_with_offset(
            self, tmp_path):
        s = store_at(tmp_path)
        s.upsert(make_task())
        s.upsert(make_task(body=b"two"))
        s.upsert(make_task(body=b"three"))
        s.close()
        path = str(tmp_path / "j")
        lines = open(path).read().splitlines()
        expected_offset = len((lines[0] + "\n").encode())
        lines[1] = lines[1][:-3] + 'xx}'  # interior record
        open(path, "w").write("\n".join(lines) + "\n")
        with pytest.raises(JournalCorruptError) as exc:
            JournaledTaskStore(path, metrics=MetricsRegistry())
        assert exc.value.offset == expected_offset
        assert "durability.md" in str(exc.value)
        # The file was NOT touched — quarantine, not silent repair.
        assert open(path).read().splitlines()[1] == lines[1]

    def test_verify_cli_verdicts(self, tmp_path, capsys):
        s = store_at(tmp_path)
        s.upsert(make_task())
        s.close()
        path = str(tmp_path / "j")
        assert jf.main([path]) == 0
        assert "OK" in capsys.readouterr().out
        with open(path, "a") as fh:
            fh.write("torn")
        assert jf.main([path]) == 0  # salvageable → boot repairs it
        assert "TORN TAIL" in capsys.readouterr().out


# -- replay compatibility ----------------------------------------------------


class TestLegacyReplay:
    def test_pre_envelope_journal_replays_and_mixes(self, tmp_path):
        """Old journals (bare JSON lines) replay verbatim; new appends
        land enveloped in the same file; the mixed file replays again."""
        path = str(tmp_path / "legacy")
        with open(path, "w", encoding="utf-8") as fh:
            fh.write(json.dumps({"TaskId": "old-1", "Endpoint": "/v1/x",
                                 "Status": "created",
                                 "BackendStatus": "created",
                                 "BodyHex": b"hello".hex()}) + "\n")
            fh.write(json.dumps({"TaskId": "old-1", "Slim": True,
                                 "Status": "completed - ok",
                                 "BackendStatus": "completed"}) + "\n")
        s = JournaledTaskStore(path, metrics=MetricsRegistry())
        assert s.get("old-1").canonical_status == "completed"
        fresh = s.upsert(make_task())
        s.close()
        raw = open(path).read().splitlines()
        assert not raw[0].startswith("J1:")      # legacy kept verbatim
        assert raw[-1].startswith("J1:")         # new append enveloped
        s2 = JournaledTaskStore(path, metrics=MetricsRegistry())
        assert s2.get("old-1").canonical_status == "completed"
        assert s2.get(fresh.task_id).canonical_status == "created"
        s2.close()

    def test_chain_head_survives_restart_and_compaction(self, tmp_path):
        s = store_at(tmp_path)
        t = s.upsert(make_task())
        s.update_status(t.task_id, "completed - x", TaskStatus.COMPLETED)
        head = s.chain_head
        s.close()
        s2 = store_at(tmp_path)
        assert s2.chain_head == head
        s2.compact()
        assert s2.chain_head != head  # new byte lineage…
        head2 = s2.chain_head
        s2.close()
        s3 = store_at(tmp_path)
        assert s3.chain_head == head2  # …that replays to the same head
        assert s3.get(t.task_id).canonical_status == "completed"
        s3.close()


# -- fsync policy ladder -----------------------------------------------------


class TestFsyncPolicies:
    @pytest.fixture()
    def fsync_counter(self, monkeypatch):
        calls = []
        real = os.fsync

        def counting(fd):
            calls.append(fd)
            return real(fd)

        monkeypatch.setattr(os, "fsync", counting)
        return calls

    def test_default_never_is_todays_write_behavior(self, tmp_path,
                                                    fsync_counter):
        """The byte-identical-default acceptance: no fsync ever issues on
        the append path, exactly the pre-hardening behavior."""
        s = store_at(tmp_path)
        assert s._fsync_kind == "never"
        for _ in range(5):
            s.upsert(make_task())
        assert fsync_counter == []
        s.close()
        assert fsync_counter == []  # nothing owed at close either

    def test_always_fsyncs_every_append(self, tmp_path, fsync_counter):
        s = store_at(tmp_path, fsync="always")
        base = len(fsync_counter)
        s.upsert(make_task())
        s.upsert(make_task())
        assert len(fsync_counter) - base == 2
        assert s.journal_stats()["fsyncs"] == 2
        s.close()

    def test_group_commit_amortizes_and_timer_completes_window(
            self, tmp_path, fsync_counter):
        s = store_at(tmp_path, fsync="group:30")
        base = len(fsync_counter)
        for _ in range(10):
            s.upsert(make_task())
        burst = len(fsync_counter) - base
        assert burst <= 3  # amortized, never one per append
        deadline = time.monotonic() + 2.0
        while s._fsync_dirty and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not s._fsync_dirty  # the timer synced the idle tail
        s.close()

    def test_env_knob_resolves_when_arg_is_none(self, tmp_path,
                                                monkeypatch):
        monkeypatch.setenv("AI4E_TASKSTORE_FSYNC", "group:50")
        s = store_at(tmp_path)
        assert (s._fsync_kind, s._fsync_group_s) == ("group", 0.05)
        s.close()
        # Explicit argument wins over the env.
        s2 = store_at(tmp_path, name="j2", fsync="never")
        assert s2._fsync_kind == "never"
        s2.close()

    def test_malformed_policy_fails_at_construction(self, tmp_path):
        with pytest.raises(ValueError):
            store_at(tmp_path, fsync="sometimes")


# -- degraded mode -----------------------------------------------------------


class TestDegradedMode:
    def _faulted_store(self, tmp_path, **rule):
        s = store_at(tmp_path)
        seeded = s.upsert(make_task(body=b"pre-fault"))
        injector = DiskFaultInjector(seed=7)
        attach_journal_faults(s, injector)
        if rule:
            injector.add_rule(**rule)
        return s, seeded, injector

    def test_enospc_on_append_rolls_back_and_fences(self, tmp_path):
        s, seeded, _ = self._faulted_store(tmp_path, op="write",
                                           errno=errno.ENOSPC)
        with pytest.raises(JournalDegradedError) as exc:
            s.upsert(make_task(body=b"doomed"))
        assert exc.value.rollback
        assert s.degraded
        # Memory never ran ahead of disk: the doomed create is GONE.
        assert len(s._tasks) == 1
        # Reads serve; every further mutation refuses with the typed
        # error BEFORE touching memory.
        assert s.get(seeded.task_id).canonical_status == "created"
        with pytest.raises(JournalDegradedError):
            s.update_status(seeded.task_id, "completed - x",
                            TaskStatus.COMPLETED)
        assert s.get(seeded.task_id).canonical_status == "created"
        with pytest.raises(JournalDegradedError):
            s.set_result(seeded.task_id, b"r")
        assert s.get_result(seeded.task_id) is None
        s.close()

    def test_update_rollback_keeps_prior_status_and_sets(self, tmp_path):
        s, seeded, _ = self._faulted_store(tmp_path, op="write",
                                           errno=errno.ENOSPC)
        with pytest.raises(JournalDegradedError):
            s.update_status(seeded.task_id, "completed - x",
                            TaskStatus.COMPLETED)
        assert s.get(seeded.task_id).canonical_status == "created"
        assert s.set_members("/v1/dur/x", "created") == [seeded.task_id]
        assert s.set_members("/v1/dur/x", "completed") == []
        s.close()

    def test_torn_write_then_recover_salvages_the_tail(self, tmp_path):
        """The fault writes a PREFIX of the record before failing (short
        write): recover() must truncate that torn tail before reopening,
        and a restart replays exactly the acknowledged history."""
        s, seeded, injector = self._faulted_store(
            tmp_path, op="write", errno=errno.ENOSPC, torn_bytes=25)
        with pytest.raises(JournalDegradedError):
            s.upsert(make_task(body=b"torn-victim"))
        assert s.degraded
        injector.clear()
        assert s.recover()
        after = s.upsert(make_task(body=b"post-recovery"))
        s.close()
        s2 = store_at(tmp_path)
        assert {t.task_id for t in s2.snapshot()} == {
            seeded.task_id, after.task_id}
        s2.close()

    def test_eio_on_fsync_keeps_memory_equal_to_file(self, tmp_path):
        s = store_at(tmp_path, fsync="always")
        injector = DiskFaultInjector(seed=7)
        attach_journal_faults(s, injector)
        injector.add_rule(op="fsync", errno=errno.EIO)
        with pytest.raises(JournalDegradedError) as exc:
            s.upsert(make_task(body=b"refused-but-durable"))
        assert not exc.value.rollback
        assert s.degraded
        # The bytes ARE in the file — the refused-but-durable residual:
        # memory keeps the record so reads here match a future replay.
        assert len(s._tasks) == 1
        injector.clear()
        assert s.recover()
        s.close()
        s2 = store_at(tmp_path)
        assert len(s2.snapshot()) == 1
        s2.close()

    def test_degraded_metrics_and_stats(self, tmp_path):
        metrics = MetricsRegistry()
        s = JournaledTaskStore(str(tmp_path / "j"), metrics=metrics)
        injector = DiskFaultInjector(seed=1)
        attach_journal_faults(s, injector)
        injector.add_rule(op="write", errno=errno.ENOSPC)
        with pytest.raises(JournalDegradedError):
            s.upsert(make_task())
        assert metrics.gauge("ai4e_journal_degraded", "").value() == 1.0
        assert metrics.counter("ai4e_journal_degraded_total", "").value(
            errno="ENOSPC") == 1
        assert s.journal_stats()["degraded"] is True
        injector.clear()
        assert s.recover()
        assert metrics.gauge("ai4e_journal_degraded", "").value() == 0.0
        s.close()

    def test_flush_failure_buffer_never_resurrects_rolled_back_record(
            self, tmp_path):
        """Review regression: write() buffers cleanly, flush() fails —
        the Python-side buffer RETAINS the refused record's bytes, and an
        ordinary close() (by recover() or shutdown) would re-flush them
        onto the healed file, resurrecting a mutation the caller was told
        was refused and unwound. The store discards the broken handle's
        buffer instead."""
        s, seeded, injector = self._faulted_store(
            tmp_path, op="flush", errno=errno.ENOSPC)
        with pytest.raises(JournalDegradedError) as exc:
            s.upsert(make_task(body=b"refused-and-unwound"))
        assert exc.value.rollback
        injector.clear()
        assert s.recover()
        # Live store: rolled back, and recovery did not resurrect it.
        assert {t.task_id for t in s.snapshot()} == {seeded.task_id}
        after = s.upsert(make_task(body=b"post-recovery"))
        s.close()
        # Restart: the refused record's bytes never reached the file —
        # neither recover()'s handle swap nor close() flushed them.
        s2 = store_at(tmp_path)
        assert {t.task_id for t in s2.snapshot()} == {
            seeded.task_id, after.task_id}
        s2.close()

    def test_flush_failure_close_while_degraded_discards_buffer(
            self, tmp_path):
        """Same hazard on the OTHER exit path: closing a degraded store
        (the sharded facade's mark_dead before replica promotion) must
        not flush the refused record where the replica drain — or a
        restart — would pick it up."""
        s, seeded, _ = self._faulted_store(tmp_path, op="flush",
                                           errno=errno.ENOSPC)
        with pytest.raises(JournalDegradedError):
            s.upsert(make_task(body=b"refused"))
        s.close()
        s2 = store_at(tmp_path)
        assert {t.task_id for t in s2.snapshot()} == {seeded.task_id}
        s2.close()

    def test_evict_append_failure_restores_the_whole_task(self, tmp_path):
        """Review regression: an eviction whose Evict append fails must
        restore the task wholesale (record, status set, orig body,
        result) — otherwise memory forgets a task the journal still
        holds, a recovered retry no-ops before journaling the eviction,
        and a restart resurrects it."""
        s = store_at(tmp_path)
        t = s.upsert(make_task(body=b"evict-me"))
        s.update_status(t.task_id, "completed - x", TaskStatus.COMPLETED)
        s.set_result(t.task_id, b"kept-result")
        injector = DiskFaultInjector(seed=3)
        attach_journal_faults(s, injector)
        injector.add_rule(op="write", errno=errno.ENOSPC)
        with pytest.raises(JournalDegradedError):
            s.evict_terminal_older_than(0.0)
        # Fully restored: record, set membership, result, original body.
        assert s.get(t.task_id).canonical_status == "completed"
        assert s.set_members("/v1/dur/x", "completed") == [t.task_id]
        assert s.get_result(t.task_id)[0] == b"kept-result"
        assert s.get_original_body(t.task_id) == b"evict-me"
        injector.clear()
        assert s.recover()
        # The retried eviction now journals and sticks across restart.
        assert s.evict_terminal_older_than(0.0) == 1
        s.close()
        s2 = store_at(tmp_path)
        with pytest.raises(TaskNotFound):
            s2.get(t.task_id)
        s2.close()

    def test_recover_salvage_bumps_generation_for_readers(self, tmp_path):
        """Review regression: recover()'s salvage truncates bytes that
        replication readers may have already consumed (a torn fragment
        streams like any other bytes) — without a generation bump, a
        reader whose offset passed the verified prefix reports zero lag
        while missing every post-recover write, or splices fresh record
        bytes onto its stale buffer and parks. The bump forces the
        full-resync path, same contract as compaction."""
        from ai4e_tpu.taskstore.sharding import ShardGroup

        group = ShardGroup(0, journal_path=str(tmp_path / "j"),
                           replicas=1)
        try:
            link = group.links[0]
            t1 = group.primary.upsert(make_task())
            assert link.sync_once() > 0
            injector = DiskFaultInjector(seed=9)
            attach_journal_faults(group.primary, injector)
            injector.add_rule(op="write", errno=errno.ENOSPC,
                              torn_bytes=10)
            with pytest.raises(JournalDegradedError):
                group.primary.upsert(make_task())
            gen_before = group.primary.journal_generation
            # The torn fragment is visible file bytes: the link consumes
            # them and its offset passes the verified prefix.
            link.sync_once()
            assert group.primary.recover()
            assert group.primary.journal_generation == gen_before + 1
            t2 = group.primary.upsert(make_task())
            while link.sync_once():
                pass
            assert link.standby.get(t1.task_id)
            assert link.standby.get(t2.task_id)
            assert (link.standby.replica_chain_head
                    == group.primary.chain_head)
        finally:
            group.close()

    def test_set_result_append_failure_keeps_prior_offloaded_result(
            self, tmp_path):
        """Review regression: superseding an offloaded result deletes the
        stale blob in the base apply — which must not happen before the
        record is known journaled. A degraded append used to roll back to
        a pointer whose blob was already gone, making an ACKNOWLEDGED
        result unreadable. Append-first leaves memory (and the blob)
        untouched on failure."""
        from ai4e_tpu.taskstore import FileResultBackend

        backend = FileResultBackend(str(tmp_path / "blobs"))
        s = store_at(tmp_path, result_backend=backend,
                     result_offload_threshold=64)
        t = s.upsert(make_task())
        big = b"\x41" * 256
        s.set_result(t.task_id, big)  # offloads: memory holds a pointer
        assert s._results[t.task_id][0] is None
        injector = DiskFaultInjector(seed=11)
        attach_journal_faults(s, injector)
        injector.add_rule(op="write", errno=errno.ENOSPC)
        # Inline supersede refused mid-append: the acknowledged result
        # must STAY readable (pointer intact, blob intact).
        with pytest.raises(JournalDegradedError):
            s.set_result(t.task_id, b"small-inline")
        assert s.get_result(t.task_id) == (big, "application/json")
        assert backend.get(t.task_id) is not None
        injector.clear()
        assert s.recover()
        # The retried supersede now lands and reaps the stale blob.
        s.set_result(t.task_id, b"small-inline")
        assert s.get_result(t.task_id)[0] == b"small-inline"
        assert backend.get(t.task_id) is None
        s.close()

    def test_set_result_pointer_rewrite_failure_never_dangles(
            self, tmp_path):
        """Pointer→pointer companion: put() overwrites the blob in place
        BEFORE the lock, so a refused append cannot restore the old
        bytes — but the visible pointer must never dangle. set_result's
        reap skips keys that already held a pointer; the documented
        residual is that the blob serves the refused write's content."""
        from ai4e_tpu.taskstore import FileResultBackend

        backend = FileResultBackend(str(tmp_path / "blobs"))
        s = store_at(tmp_path, result_backend=backend,
                     result_offload_threshold=64)
        t = s.upsert(make_task())
        s.set_result(t.task_id, b"\x41" * 256)
        injector = DiskFaultInjector(seed=11)
        attach_journal_faults(s, injector)
        injector.add_rule(op="write", errno=errno.ENOSPC)
        with pytest.raises(JournalDegradedError):
            s.set_result(t.task_id, b"\x42" * 256)
        # Readable — never a pointer to a deleted blob (the residual:
        # content is the refused write's, docs/durability.md).
        found = s.get_result(t.task_id)
        assert found is not None and found[0] == b"\x42" * 256
        s.close()

    def test_fsync_failure_result_applies_memory_and_keeps_blob(
            self, tmp_path):
        """Review regression: append-first must not invert the
        rollback=False contract. EIO on fsync lands the Result record
        durably in the file; memory must still apply it (memory == file,
        the refused-but-possibly-durable residual upsert/update keep)
        and the cleanup must NOT reap the blob the durable record points
        to — a restart would otherwise replay a result pointer whose
        blob is gone and serve None for a journaled result."""
        from ai4e_tpu.taskstore import FileResultBackend

        backend = FileResultBackend(str(tmp_path / "blobs"))
        s = store_at(tmp_path, fsync="always", result_backend=backend,
                     result_offload_threshold=64)
        t = s.upsert(make_task())
        injector = DiskFaultInjector(seed=13)
        attach_journal_faults(s, injector)
        injector.add_rule(op="fsync", errno=errno.EIO)
        big = b"\x44" * 256
        with pytest.raises(JournalDegradedError) as exc:
            s.set_result(t.task_id, big)
        assert not exc.value.rollback
        # Memory == file: the result is visible and its blob survives.
        assert s.get_result(t.task_id) == (big, "application/json")
        assert backend.get(t.task_id) is not None
        s.close()
        # The durable record replays WITH a readable blob.
        s2 = store_at(tmp_path, result_backend=backend,
                      result_offload_threshold=64)
        assert s2.get_result(t.task_id) == (big, "application/json")
        s2.close()

    def test_evict_mid_batch_degraded_reaps_journaled_victims_blobs(
            self, tmp_path):
        """Review regression: a mid-batch degraded abort used to skip the
        blob-delete loop for victims already evicted AND journaled — no
        journal record references their blobs anymore, so nothing would
        ever delete them (a permanent orphan on the mount)."""
        from ai4e_tpu.taskstore import FileResultBackend

        backend = FileResultBackend(str(tmp_path / "blobs"))
        s = store_at(tmp_path, result_backend=backend,
                     result_offload_threshold=64)
        tasks = []
        for _ in range(2):
            t = s.upsert(make_task())
            s.update_status(t.task_id, "completed - x",
                            TaskStatus.COMPLETED)
            s.set_result(t.task_id, b"\x43" * 256)  # offloaded
            tasks.append(t)
        injector = DiskFaultInjector(seed=5)
        attach_journal_faults(s, injector)
        # First Evict append lands; the second one faults.
        injector.add_rule(op="write", errno=errno.ENOSPC, after_ops=1)
        with pytest.raises(JournalDegradedError):
            s.evict_terminal_older_than(0.0)
        # Victim 1: evicted, journaled — its orphaned blob WAS deleted.
        with pytest.raises(TaskNotFound):
            s.get(tasks[0].task_id)
        assert backend.get(tasks[0].task_id) is None
        # Victim 2: rolled back wholesale — record AND blob intact.
        assert s.get(tasks[1].task_id).canonical_status == "completed"
        assert s.get_result(tasks[1].task_id)[0] == b"\x43" * 256
        s.close()

    def test_http_surface_answers_typed_503(self, tmp_path):
        async def main():
            s = store_at(tmp_path)
            seeded = s.upsert(make_task())
            injector = DiskFaultInjector(seed=1)
            attach_journal_faults(s, injector)
            injector.add_rule(op="write", errno=errno.ENOSPC,
                              times=None)
            client = await serve(make_app(s))
            try:
                resp = await client.post("/v1/taskstore/upsert", json={
                    "Endpoint": "/v1/dur/x", "Status": "created"})
                assert resp.status == 503
                assert resp.headers["X-Shed-Reason"] == "journal-degraded"
                assert "X-Not-Primary" not in resp.headers  # reads stay
                resp = await client.post("/v1/taskstore/update", json={
                    "TaskId": seeded.task_id, "Status": "completed - x"})
                assert resp.status == 503
                assert resp.headers["X-Shed-Reason"] == "journal-degraded"
                # Reads keep serving through the degradation.
                resp = await client.get(
                    f"/v1/taskstore/task?taskId={seeded.task_id}")
                assert resp.status == 200
                # The role endpoint names the state + the chain head.
                resp = await client.get("/v1/taskstore/role")
                doc = await resp.json()
                assert doc["degraded"] is True
                assert doc["chain_head"] == s.chain_head
            finally:
                await client.close()
                s.close()

        run(main())


# -- verified replication ----------------------------------------------------


class TestVerifiedAbsorb:
    def _primary_lines(self, tmp_path, n=3):
        p = store_at(tmp_path, name="p")
        tasks = [p.upsert(make_task(body=f"b{i}".encode()))
                 for i in range(n)]
        lines = [ln.rstrip("\n")
                 for ln in open(str(tmp_path / "p")) if ln.strip()]
        return p, tasks, lines

    def test_absorb_verifies_and_converges_chain_heads(self, tmp_path):
        p, tasks, lines = self._primary_lines(tmp_path)
        f = FollowerTaskStore(str(tmp_path / "f"),
                              metrics=MetricsRegistry())
        f.reset()
        f.absorb_lines(lines)
        assert f.replica_chain_head == p.chain_head
        for t in tasks:
            assert f.get(t.task_id)
        # The follower's own file is self-consistent: restart replays it.
        f.close()
        f2 = FollowerTaskStore(str(tmp_path / "f"),
                               metrics=MetricsRegistry())
        for t in tasks:
            assert f2.get(t.task_id)
        f2.close()
        p.close()

    def test_corrupt_streamed_line_refused_prefix_kept(self, tmp_path):
        p, tasks, lines = self._primary_lines(tmp_path)
        metrics = MetricsRegistry()
        f = FollowerTaskStore(str(tmp_path / "f"), metrics=metrics)
        f.reset()
        bad = lines[1][:-3] + 'xx}'
        with pytest.raises(JournalCorruptError):
            f.absorb_lines([lines[0], bad, lines[2]])
        # The verified prefix applied; the bad line and its successors
        # did NOT absorb silently.
        assert f.get(tasks[0].task_id)
        with pytest.raises(TaskNotFound):
            f.get(tasks[1].task_id)
        with pytest.raises(TaskNotFound):
            f.get(tasks[2].task_id)
        assert metrics.counter(
            "ai4e_journal_verify_failures_total", "").value() == 1
        f.close()
        p.close()

    def test_checksumless_legacy_lines_absorb_for_migration(self, tmp_path):
        f = FollowerTaskStore(str(tmp_path / "f"),
                              metrics=MetricsRegistry())
        f.reset()
        f.absorb_lines([json.dumps({"TaskId": "legacy-1",
                                    "Endpoint": "/v1/x",
                                    "Status": "created",
                                    "BackendStatus": "created"})])
        assert f.get("legacy-1").canonical_status == "created"
        f.close()

    def test_parked_replica_link_unparks_on_generation_resync(
            self, tmp_path):
        """Review regression: a link parked on a verified-corrupt record
        kept its park tuple across a generation resync — a stale
        (generation, offset) pair could later match a fresh one exactly
        and silently stall a healthy replica forever (sync_once
        returning 0 with no log line). The resync branch clears it."""
        from ai4e_tpu.taskstore.sharding import ShardGroup

        group = ShardGroup(0, journal_path=str(tmp_path / "j"),
                           replicas=1)
        try:
            link = group.links[0]
            t = group.primary.upsert(make_task())
            assert link.sync_once() > 0
            # Bit-rot appended behind the store's back: the link parks.
            with open(group.journal_path, "a") as fh:
                fh.write("## bit-rot, not a journal line ##\n")
            assert link.sync_once() == 0
            assert link._corrupt_at is not None
            assert link.sync_once() == 0  # parked: no re-read
            # Compaction rewrites clean bytes at a new generation: the
            # link resyncs AND drops the stale park.
            group.primary.compact()
            assert link.sync_once() > 0
            assert link._corrupt_at is None
            assert (link.standby.replica_chain_head
                    == group.primary.chain_head)
            assert link.standby.get(t.task_id)
        finally:
            group.close()

    def test_role_endpoint_exposes_replica_chain_head(self, tmp_path):
        """Review regression: the HTTP divergence check must compare the
        primary's chain_head to the FOLLOWER's replica_chain_head. A
        re-seeded follower's OWN file legitimately diverges (reset writes
        its epoch line), so exposing only chain_head read as a permanent
        false divergence on a perfectly converged pair."""
        async def main():
            p, tasks, lines = self._primary_lines(tmp_path)
            f = FollowerTaskStore(str(tmp_path / "f"),
                                  metrics=MetricsRegistry())
            f.demote(1)  # fenced once — the post-failover shape
            f.reset()    # re-seed: writes the epoch line, forking own file
            f.absorb_lines(lines)
            client = await serve(make_app(f))
            try:
                doc = await (await client.get("/v1/taskstore/role")).json()
                # The comparable pair converges...
                assert doc["replica_chain_head"] == p.chain_head
                # ...while the naive own-file comparison never would.
                assert doc["chain_head"] != p.chain_head
            finally:
                await client.close()
                f.close()
                p.close()

        run(main())

    def test_streamed_corruption_forces_generation_resync(self, tmp_path):
        """Satellite: a checksum-failing line in the HTTP journal stream
        must force the follower's generation-mismatch resync path — and
        once the primary's compaction rewrites a clean generation, the
        follower converges instead of holding poisoned state."""
        async def main():
            primary = store_at(tmp_path, name="p")
            t1 = primary.upsert(make_task(body=b"one"))
            client = await serve(make_app(primary))
            follower = FollowerTaskStore(str(tmp_path / "f"),
                                         metrics=MetricsRegistry())
            repl = JournalReplicator(follower, str(client.make_url("")),
                                     poll_wait=0.2)
            repl.start()
            try:
                assert await wait_for(
                    lambda: follower.replica_chain_head
                    == primary.chain_head)
                # Corrupt the stream at the source: garbage appended to
                # the primary's FILE behind the store's back.
                with open(str(tmp_path / "p"), "a") as fh:
                    fh.write("## bit-rot, not a journal line ##\n")
                gen_before = primary.journal_generation
                assert await wait_for(lambda: repl.generation == -1)
                assert not repl.synced.is_set()
                # The primary compacts (its memory is the clean truth):
                # new generation, clean bytes — the follower resyncs and
                # converges.
                t2 = primary.upsert(make_task(body=b"two"))
                primary.compact()
                assert primary.journal_generation > gen_before
                assert await wait_for(
                    lambda: follower.replica_chain_head
                    == primary.chain_head)
                assert follower.get(t1.task_id)
                assert follower.get(t2.task_id)
            finally:
                await repl.aclose()
                await client.close()
                follower.close()
                primary.close()

        run(main())


# -- assembly defaults -------------------------------------------------------


class TestAssemblyDefaults:
    def test_platform_default_policy_is_never_and_env_resolves(
            self, tmp_path, monkeypatch):
        from ai4e_tpu.platform_assembly import LocalPlatform, PlatformConfig
        platform = LocalPlatform(
            PlatformConfig(journal_path=str(tmp_path / "j")),
            metrics=MetricsRegistry())
        assert platform.store._fsync_kind == "never"
        platform.store.close()
        monkeypatch.setenv("AI4E_TASKSTORE_FSYNC", "always")
        platform2 = LocalPlatform(
            PlatformConfig(journal_path=str(tmp_path / "j2")),
            metrics=MetricsRegistry())
        assert platform2.store._fsync_kind == "always"
        platform2.store.close()

    def test_replicaless_degraded_shard_keeps_serving_reads(self, tmp_path):
        """Review regression: with NO promotable replica, a degraded
        shard primary must NOT be closed by the facade — that would turn
        a transient disk fault into a permanent full-shard outage. The
        typed error surfaces, reads keep serving, and recover() re-admits
        writes."""
        import errno as errno_mod

        from ai4e_tpu.taskstore.sharding import ShardedTaskStore
        store = ShardedTaskStore(2, journal_path=str(tmp_path / "j"),
                                 replicas=0, metrics=MetricsRegistry())
        t = store.upsert(make_task())
        victim = store.groups[store.shard_for(t.task_id)]
        injector = DiskFaultInjector(seed=5)
        attach_journal_faults(victim.active, injector)
        injector.add_rule(op="write", errno=errno_mod.ENOSPC, times=None)
        with pytest.raises(JournalDegradedError):
            store.update_status(t.task_id, "completed - x",
                                TaskStatus.COMPLETED)
        # NOT closed, NOT marked dead: reads still route and serve.
        assert not victim.dead
        assert store.get(t.task_id).canonical_status == "created"
        # Disk heals → the shard re-admits writes in place.
        injector.clear()
        assert victim.active.recover()
        store.update_status(t.task_id, "completed - x",
                            TaskStatus.COMPLETED)
        assert store.get(t.task_id).canonical_status == "completed"
        store.close()

    def test_sharded_topology_exposes_chain_heads(self, tmp_path):
        from ai4e_tpu.taskstore.sharding import ShardedTaskStore
        store = ShardedTaskStore(2, journal_path=str(tmp_path / "j"),
                                 replicas=1, metrics=MetricsRegistry())
        t = store.upsert(make_task())
        for group in store.groups:
            for link in group.links:
                link.drain()
        topo = store.topology()
        owner = store.shard_for(t.task_id)
        g = topo["groups"][owner]
        assert g["chain_head"] == store.groups[owner].active.chain_head
        assert g["replica_chain_heads"] == [
            store.groups[owner].active.chain_head]
        assert g["degraded"] is False
        assert store.journal_stats()["bytes_appended"] > 0
        store.close()

    def test_out_of_band_knob_survives_config_from_env(self, monkeypatch):
        from ai4e_tpu.config import FrameworkConfig
        monkeypatch.setenv("AI4E_TASKSTORE_FSYNC", "group:25")
        FrameworkConfig.from_env()  # must not raise unknown-section


# -- review regressions: degraded promote / evict-fsync blob reap ------------


class TestDegradedPromotion:
    def _follower(self, tmp_path, **kw):
        s = FollowerTaskStore(str(tmp_path / "f"),
                              metrics=MetricsRegistry(), **kw)
        injector = DiskFaultInjector(seed=13)
        attach_journal_faults(s, injector)
        return s, injector

    def test_promote_epoch_append_failure_unwinds_wholesale(self, tmp_path):
        """Review regression: a half-promoted store (role flipped, epoch
        minted in memory, Epoch record never in the file) breaks the
        no-two-promotions-share-an-epoch fencing guarantee — a restart
        replays the OLD epoch and a later promotion re-mints one the
        deposed lineage already claimed. The failed promote must unwind
        wholesale, and recover() + a retried promote() must mint
        cleanly."""
        s, injector = self._follower(tmp_path)
        injector.add_rule(op="write", errno=errno.ENOSPC)
        with pytest.raises(JournalDegradedError) as exc:
            s.promote()
        assert exc.value.rollback
        # Unwound: still an intact (degraded) follower at epoch 0.
        assert s.role == "follower"
        assert s.epoch == 0
        assert s._journal is None
        injector.clear()
        assert s.recover()
        s.promote()
        assert s.role == "primary" and s.epoch == 1
        created = s.upsert(make_task())
        s.close()
        # Restart replays exactly one minted epoch + the write.
        s2 = FollowerTaskStore(str(tmp_path / "f"), start_as_primary=True,
                               metrics=MetricsRegistry())
        assert s2.epoch == 1
        assert s2.get(created.task_id).canonical_status == "created"
        s2.close()

    def test_promote_fsync_failure_is_durable_and_degraded(self, tmp_path):
        """rollback=False companion: the Epoch record IS in the file, so
        the promotion is complete — promote() returns, the store is
        primary at epoch 1 and degraded (mutations refuse typed)."""
        s, injector = self._follower(tmp_path, fsync="always")
        injector.add_rule(op="fsync", errno=errno.EIO)
        s.promote()  # must NOT raise
        assert s.role == "primary" and s.epoch == 1
        assert s.degraded
        with pytest.raises(JournalDegradedError):
            s.upsert(make_task())
        s.close()
        s2 = FollowerTaskStore(str(tmp_path / "f"), start_as_primary=True,
                               metrics=MetricsRegistry())
        assert s2.epoch == 1  # the mint survived the restart
        s2.close()

    def test_failover_skips_replica_whose_disk_faults_mid_promotion(
            self, tmp_path):
        """Review regression: _fail_over used to let a standby's own
        JournalDegradedError escape AFTER popping it from the links —
        aborting the failover and silently discarding the replica. It
        must try the next replica instead."""
        from ai4e_tpu.taskstore.sharding import ShardedTaskStore
        store = ShardedTaskStore(1, journal_path=str(tmp_path / "s"),
                                 replicas=2, metrics=MetricsRegistry())
        t = store.upsert(make_task())
        group = store.groups[0]
        for link in group.links:
            link.drain()
        second = group.links[1].standby
        bad = DiskFaultInjector(seed=3)
        attach_journal_faults(group.links[0].standby, bad)
        bad.add_rule(op="write", errno=errno.ENOSPC, times=None)
        store.kill_shard_primary(0)
        store.update_status(t.task_id, "completed - x",
                            TaskStatus.COMPLETED)
        assert group.active is second
        assert second.epoch == 1
        assert not group.links  # the faulted replica was consumed
        assert store.get(t.task_id).canonical_status == "completed"
        store.close()


class TestEvictFsyncFailure:
    def test_evict_fsync_failure_still_reaps_blobs(self, tmp_path):
        """Review regression: on the fsync-failure shape the Evict record
        is in the file and memory already forgot the task — raising out
        of _apply_evict dropped the victim's blob keys on the floor,
        orphaning its offloaded result on the mount forever. The
        completed eviction must surrender its keys to the delete loop."""
        from ai4e_tpu.taskstore import FileResultBackend

        backend = FileResultBackend(str(tmp_path / "blobs"))
        s = store_at(tmp_path, fsync="always", result_backend=backend,
                     result_offload_threshold=64)
        t = s.upsert(make_task())
        s.update_status(t.task_id, "completed - x", TaskStatus.COMPLETED)
        s.set_result(t.task_id, b"\x44" * 256)  # offloaded
        assert backend.get(t.task_id) is not None
        injector = DiskFaultInjector(seed=9)
        attach_journal_faults(s, injector)
        injector.add_rule(op="fsync", errno=errno.EIO)
        # The eviction completes (record in file, memory forgot it) —
        # no raise, and the orphaned blob is reaped.
        assert s.evict_terminal_older_than(0.0) == 1
        assert s.degraded
        with pytest.raises(TaskNotFound):
            s.get(t.task_id)
        assert backend.get(t.task_id) is None
        s.close()
        # Restart agrees: the journaled Evict record replays the task away.
        s2 = store_at(tmp_path, result_backend=backend)
        assert s2.snapshot() == []
        s2.close()
