"""Checkpoint store: atomicity, retention, async, elastic restore —
plus the journal's crash-fuzz contract and the follower cursor."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (BlobLog, BlobLogFollower, CheckpointManager,
                              latest_step, restore_state, save_state)


def make_state(seed=0):
    rng = np.random.RandomState(seed)
    return {"params": {"w": jnp.asarray(rng.randn(8, 16), jnp.float32),
                       "stack": {"k": jnp.asarray(rng.randn(3, 4, 4),
                                                  jnp.float32)}},
            "opt": {"m": jnp.zeros((8, 16)), "count": jnp.asarray(7)},
            "step": jnp.asarray(100)}


class TestStore:
    def test_roundtrip(self, tmp_path):
        st = make_state()
        save_state(st, str(tmp_path), 100)
        assert latest_step(str(tmp_path)) == 100
        rt = restore_state(st, str(tmp_path), 100)
        for a, b in zip(jax.tree_util.tree_leaves(st),
                        jax.tree_util.tree_leaves(rt)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_atomic_no_partial(self, tmp_path):
        st = make_state()
        save_state(st, str(tmp_path), 1)
        # a stale .tmp directory must never count as a checkpoint
        os.makedirs(tmp_path / "step_00000002.tmp", exist_ok=True)
        assert latest_step(str(tmp_path)) == 1

    def test_shape_mismatch_rejected(self, tmp_path):
        st = make_state()
        save_state(st, str(tmp_path), 5)
        bad = dict(st, step=jnp.zeros((2,)))
        with pytest.raises(ValueError):
            restore_state(bad, str(tmp_path), 5)


class TestManager:
    def test_async_save_and_restore_latest(self, tmp_path):
        m = CheckpointManager(str(tmp_path), keep=2)
        st = make_state()
        m.save(st, 10)
        m.save(st, 20)
        m.wait()
        restored, step = m.restore_latest(st)
        assert step == 20
        np.testing.assert_array_equal(
            np.asarray(restored["params"]["w"]),
            np.asarray(st["params"]["w"]))

    def test_retention(self, tmp_path):
        m = CheckpointManager(str(tmp_path), keep=2)
        st = make_state()
        for s in (1, 2, 3, 4):
            m.save(st, s, blocking=True)
        kept = sorted(d for d in os.listdir(tmp_path)
                      if d.startswith("step_"))
        assert kept == ["step_00000003", "step_00000004"]

    def test_restore_none_when_empty(self, tmp_path):
        m = CheckpointManager(str(tmp_path))
        restored, step = m.restore_latest(make_state())
        assert restored is None and step is None

    def test_elastic_restore_with_shardings(self, tmp_path):
        """Restore onto an explicit sharding (single-device here; the
        512-device equivalence is exercised by the dry-run path)."""
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 1), ("data", "model"))
        from repro.dist.sharding import named, param_specs
        st = make_state()
        m = CheckpointManager(str(tmp_path))
        m.save(st, 50, blocking=True)
        sh = named(param_specs(st, mesh), mesh)
        restored, step = m.restore_latest(st, shardings=sh)
        assert step == 50
        assert restored["params"]["w"].sharding is not None


# ===========================================================================
def _small_journal(path):
    """A journal of four distinct records, small enough that the fuzz
    sweeps below can afford every single-byte mutation."""
    log = BlobLog(str(path))
    recs = [("submit", {"id": i, "gen_len": 4 + i}) for i in range(3)]
    recs.append(("block", 4))
    for r in recs:
        log.append(r)
    log.close()
    return recs


class TestJournalCrashFuzz:
    """Every byte-level mutation of a journal must yield either a clean
    torn-tail truncation (a strict prefix of the original records) or
    an explicit corruption error — NEVER a silent misparse.  This is
    the promise the standby's byte-identity rests on: a journal that
    opens clean replays true history."""

    def _check(self, path, recs):
        """Open the mutated journal; it must either refuse loudly or
        produce a strict prefix of the true record sequence."""
        try:
            log = BlobLog(str(path))
        except (IOError, OSError):
            return "refused"
        got = log.read()
        log.close()
        assert got == recs[:len(got)], \
            "journal misparsed a mutated file into non-prefix records"
        return "prefix"

    def test_truncation_at_every_byte_offset(self, tmp_path):
        recs = _small_journal(tmp_path / "j.log")
        data = (tmp_path / "j.log").read_bytes()
        outcomes = set()
        for cut in range(len(data) + 1):
            p = tmp_path / f"t{cut}.log"
            p.write_bytes(data[:cut])
            outcomes.add(self._check(p, recs))
        # truncation is exactly what a torn tail looks like: every cut
        # must open as a clean prefix, none may be refused
        assert outcomes == {"prefix"}

    def test_bit_flip_at_every_byte_offset(self, tmp_path):
        recs = _small_journal(tmp_path / "j.log")
        data = bytearray((tmp_path / "j.log").read_bytes())
        outcomes = set()
        for off in range(len(data)):
            mutated = bytearray(data)
            mutated[off] ^= 0x80
            p = tmp_path / f"f{off}.log"
            p.write_bytes(bytes(mutated))
            outcomes.add(self._check(p, recs))
        # both outcomes occur across the sweep (a flip in the last
        # frame's bytes is a torn tail; earlier damage must refuse),
        # and no flip anywhere silently misparses (asserted per-file)
        assert outcomes == {"prefix", "refused"}

    def test_flip_then_append_never_drops_committed_history(self,
                                                            tmp_path):
        """The killer case for a length-bound check alone: a flip that
        ENLARGES a mid-file length field makes everything after it look
        like one giant torn frame.  The resync scan must spot the
        intact committed frames inside the 'tail' and refuse."""
        path = tmp_path / "j.log"
        recs = _small_journal(path)
        data = bytearray(path.read_bytes())
        # enlarge record 0's length field (low byte of the u32)
        data[0] ^= 0x40
        path.write_bytes(bytes(data))
        assert len(recs) == 4                  # all committed, none torn
        with pytest.raises(IOError, match="corrupt"):
            BlobLog(str(path))


class TestBlobLogFollower:
    def test_poll_tails_incremental_appends(self, tmp_path):
        log = BlobLog(str(tmp_path / "j.log"))
        f = log.follow()
        assert f.poll() == []
        log.append("a")
        log.append("b")
        assert f.poll() == ["a", "b"]
        assert f.poll() == []
        log.append("c")
        assert f.poll(max_records=1) == ["c"]
        assert (f.count, log.count) == (3, 3)
        log.close()

    def test_short_frame_is_an_append_in_flight(self, tmp_path):
        """A half-written frame at the tail is NOT an error for a
        follower — the writer is mid-append; the cursor holds and the
        record arrives whole on a later poll."""
        path = tmp_path / "j.log"
        log = BlobLog(str(path))
        log.append("whole")
        log.close()
        with open(path, "ab") as fh:
            fh.write(b"\x40\x00\x00\x00\x99")  # header + 1 of 64 bytes
        f = BlobLogFollower(str(path))
        assert f.poll() == ["whole"]
        assert f.poll() == []                  # waits, no error

    def test_complete_frame_crc_mismatch_raises(self, tmp_path):
        path = tmp_path / "j.log"
        log = BlobLog(str(path))
        log.append("one")
        off = os.path.getsize(path)
        log.append("two" * 10)
        log.close()
        with open(path, "r+b") as fh:
            fh.seek(off + 8)
            b = fh.read(1)
            fh.seek(off + 8)
            fh.write(bytes([b[0] ^ 0xFF]))
        f = BlobLogFollower(str(path))
        with pytest.raises(IOError, match="CRC"):
            f.poll()

    def test_missing_file_polls_empty(self, tmp_path):
        f = BlobLogFollower(str(tmp_path / "nope.log"))
        assert f.poll() == []
