"""Streaming file source + profiling hooks."""

import os
import threading
import time

import numpy as np
import pytest

from mmlspark_tpu.io.streaming import FileStreamSource


class TestFileStreamSource:
    def test_picks_up_new_files(self, tmp_path):
        (tmp_path / "a.bin").write_bytes(b"one")
        src = FileStreamSource(str(tmp_path), poll_interval=0.05)
        it = src.batches()
        first = next(it)
        assert list(first["bytes"]) == [b"one"]
        (tmp_path / "b.bin").write_bytes(b"two")
        (tmp_path / "c.bin").write_bytes(b"three")
        second = next(it)
        assert sorted(second["bytes"]) == [b"three", b"two"]
        src.stop()

    def test_idle_timeout_and_max_batches(self, tmp_path):
        (tmp_path / "a.bin").write_bytes(b"x")
        src = FileStreamSource(str(tmp_path), poll_interval=0.05)
        batches = list(src.batches(idle_timeout=0.3))
        assert len(batches) == 1  # then timed out

    def test_corrupt_zip_quarantined_not_busy_loop(self, tmp_path):
        """A persistently unreadable file must neither kill the stream
        nor pin the poller in a rescan busy loop; after
        ``max_read_failures`` attempts it is quarantined and good files
        keep flowing."""
        bad = tmp_path / "bad.zip"
        bad.write_bytes(b"PK\x03\x04 this is not really a zip")
        src = FileStreamSource(str(tmp_path), poll_interval=0.01,
                               inspect_zip=True)
        # all-failed cycles: generator stays alive and honors idle_timeout
        t0 = time.monotonic()
        batches = list(src.batches(idle_timeout=0.25))
        assert batches == []
        assert time.monotonic() - t0 >= 0.25  # waited, didn't spin/raise
        assert not src._fail_counts  # moved into _quarantined (in-memory)
        # a good file arriving afterwards still flows
        (tmp_path / "good.bin").write_bytes(b"ok")
        out = next(src.batches())
        assert list(out["bytes"]) == [b"ok"]
        src.stop()

    def test_checkpoint_resume(self, tmp_path):
        data_dir = tmp_path / "data"
        data_dir.mkdir()
        ckpt = str(tmp_path / "progress.json")
        (data_dir / "a.bin").write_bytes(b"old")
        src = FileStreamSource(str(data_dir), poll_interval=0.05,
                               checkpoint_location=ckpt)
        # drain the generator: the journal commits when the consumer
        # finishes a batch (at-least-once), not at yield time
        batches = list(src.batches(max_batches=1))
        assert batches[0].num_rows == 1
        src.stop()
        # restart: journaled file must be skipped, only the new one shows
        (data_dir / "b.bin").write_bytes(b"new")
        src2 = FileStreamSource(str(data_dir), poll_interval=0.05,
                                checkpoint_location=ckpt)
        batch = next(src2.batches())
        assert [os.path.basename(p) for p in batch["path"]] == ["b.bin"]
        src2.stop()

    def test_foreach_batch(self, tmp_path):
        got = []
        lock = threading.Lock()
        src = FileStreamSource(str(tmp_path), poll_interval=0.05)

        def collect(df):
            with lock:
                got.extend(df["bytes"])

        t = src.foreach_batch(collect)
        (tmp_path / "x.bin").write_bytes(b"payload")
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            with lock:
                if got:
                    break
            time.sleep(0.02)
        src.stop()
        t.join(timeout=2)
        assert got == [b"payload"]


class TestProfiling:
    def test_span(self):
        from mmlspark_tpu.core.profiling import collect, span
        with collect() as spans:
            with span("unit-test-span", k=1) as sp:
                time.sleep(0.01)
        assert sp.seconds >= 0.01
        assert spans == [("unit-test-span", sp.t0, sp.t1, {"k": 1})]
        with span("unowned") as sp:      # no owner: the tuple is dropped
            pass
        assert sp.t1 >= sp.t0 > 0 and spans[1:] == []

    @pytest.mark.slow
    def test_device_trace_writes(self, tmp_path):
        import jax.numpy as jnp
        from mmlspark_tpu.core.profiling import device_trace
        with device_trace(str(tmp_path)):
            jnp.ones(8).sum().block_until_ready()
        assert any(tmp_path.rglob("*"))


class TestForeachBatchErrors:
    def test_consumer_exception_is_terminal_and_surfaced(self, tmp_path):
        """A raising consumer used to kill the daemon thread silently —
        now it's counted, logged, and terminal on the handle."""
        src = FileStreamSource(str(tmp_path), poll_interval=0.02)

        def boom(df):
            raise ValueError("consumer bug")

        handle = src.foreach_batch(boom)
        assert handle.state == "running"
        (tmp_path / "x.bin").write_bytes(b"payload")
        handle.join(timeout=5)
        assert not handle.is_alive()
        assert handle.state == "failed"
        assert isinstance(handle.error, ValueError)
        assert handle.n_errors == 1
        assert handle.n_batches == 0             # failed batch not counted
        assert "consumer bug" in handle.status()["error"]
        src.stop()

    def test_clean_termination_reports_batches(self, tmp_path):
        (tmp_path / "a.bin").write_bytes(b"one")
        src = FileStreamSource(str(tmp_path), poll_interval=0.02)
        got = []
        handle = src.foreach_batch(got.append, max_batches=1)
        handle.join(timeout=5)
        assert handle.state == "terminated"
        assert handle.error is None
        assert handle.n_batches == 1 and len(got) == 1
        src.stop()

    def test_failed_batch_not_journaled_restart_reoffers(self, tmp_path):
        data = tmp_path / "data"
        data.mkdir()
        ckpt = str(tmp_path / "progress.json")
        (data / "a.bin").write_bytes(b"one")
        src = FileStreamSource(str(data), poll_interval=0.02,
                               checkpoint_location=ckpt)

        def boom(df):
            raise RuntimeError("no")

        handle = src.foreach_batch(boom)
        handle.join(timeout=5)
        assert handle.state == "failed"
        src.stop()
        # the failed batch was never journaled: a restart re-offers it
        src2 = FileStreamSource(str(data), poll_interval=0.02,
                                checkpoint_location=ckpt)
        batch = next(src2.batches())
        assert list(batch["bytes"]) == [b"one"]
        src2.stop()


class TestCheckpointCompaction:
    def test_dead_paths_compact_out_of_seen_and_journal(self, tmp_path):
        """The _seen set grew one key per file FOREVER; entries whose
        path left the disk now compact away at checkpoint time while
        live files keep their resume semantics."""
        import json as _json

        data = tmp_path / "data"
        data.mkdir()
        ckpt = str(tmp_path / "progress.json")
        for i in range(5):
            (data / f"f{i}.bin").write_bytes(b"x")
        src = FileStreamSource(str(data), poll_interval=0.02,
                               checkpoint_location=ckpt)
        list(src.batches(max_batches=1))
        assert len(src._seen) == 5
        # a rolling producer deletes consumed files
        for i in range(4):
            (data / f"f{i}.bin").unlink()
        (data / "new.bin").write_bytes(b"y")
        # drain the generator: the journal commits AFTER the consumer
        # finishes a batch, and compaction rides that commit
        [batch] = list(src.batches(max_batches=1))
        assert os.path.basename(batch["path"][0]) == "new.bin"
        # compacted: only the two LIVE files' keys remain (f4 + new)
        assert len(src._seen) == 2
        journal = set(_json.load(open(ckpt)))
        assert len(journal) == 2
        assert all(os.path.exists(k.rsplit(":", 2)[0]) for k in journal)
        src.stop()

    def test_compaction_applies_on_journal_load(self, tmp_path):
        import json as _json

        data = tmp_path / "data"
        data.mkdir()
        ckpt = tmp_path / "progress.json"
        (data / "live.bin").write_bytes(b"x")
        live_key = None
        src = FileStreamSource(str(data), poll_interval=0.02,
                               checkpoint_location=str(ckpt))
        list(src.batches(max_batches=1))
        live_key = next(iter(src._seen))
        src.stop()
        # fake a journal bloated with dead entries from older runs
        dead = [f"{data}/gone{i}.bin:123:456" for i in range(100)]
        ckpt.write_text(_json.dumps(dead + [live_key]))
        src2 = FileStreamSource(str(data), poll_interval=0.02,
                                checkpoint_location=str(ckpt))
        assert src2._seen == {live_key}          # dead entries dropped
        # and the live file is still NOT re-offered
        batches = list(src2.batches(idle_timeout=0.2))
        assert batches == []
        src2.stop()
