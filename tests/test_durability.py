"""Durable optimization window (PR 9): spill journal codec + parse
semantics, diverted-stream verification, and end-to-end preempt/resume
convergence of ``CannyFS.enable_spill`` / ``CannyFS.resume``."""
import pytest

from repro.core import (CannyFS, EagerFlags, FaultInjectingBackend,
                        FaultPlan, FaultRule, InMemoryBackend, ProcessKilled,
                        Transaction, TransactionFailedError, commit_marker_ok,
                        run_transaction)
from repro.core.durability import (SpillImage, _assemble, _dec, _enc,
                                   _verify)

# ---------------------------------------------------------------------------
# marker + record codec
# ---------------------------------------------------------------------------

def test_commit_marker_ok():
    assert commit_marker_ok(b"7", 7)
    assert not commit_marker_ok(b"7", 8)
    assert not commit_marker_ok(b"", 0)          # empty marker: not a commit
    assert not commit_marker_ok(b"abc", 0)
    assert not commit_marker_ok(b"\xff\xfe", 0)  # undecodable


def test_codec_roundtrip():
    rec = {"t": "done", "e": 3, "k": "write", "p": ["a/b"],
           "segs": [[0, 4, 123]]}
    line = _enc(rec)
    assert line.endswith(b"\n")
    assert _dec(line.rstrip(b"\n")) == rec


def test_codec_rejects_corruption():
    line = _enc({"t": "admit", "e": 0, "k": "mkdir", "p": ["d"]})
    # flip one payload byte: crc no longer matches
    torn = bytearray(line)
    torn[5] ^= 0x01
    assert _dec(bytes(torn).rstrip(b"\n")) is None
    # truncated line (no crc suffix)
    assert _dec(line[: len(line) // 2]) is None
    assert _dec(b"not json at all|deadbeef") is None
    assert _dec(b"[1,2,3]|" + _enc({}).rsplit(b"|", 1)[1].rstrip(b"\n")) \
        is None  # valid json, but not an object


# ---------------------------------------------------------------------------
# parse: monotone prefix, epoch scoping, uncertainty
# ---------------------------------------------------------------------------

def _log(*recs):
    return b"".join(_enc(r) for r in recs)


def test_parse_stops_at_corruption():
    good = _log({"t": "begin", "e": 0},
                {"t": "done", "e": 0, "k": "mkdir", "p": ["d"]})
    bad = b"garbage line\n" + _enc(
        {"t": "done", "e": 0, "k": "mkdir", "p": ["d2"]})
    img = SpillImage.parse(good + bad)
    assert img.began
    assert img.durable_dirs == {"d"}      # nothing after the gap is trusted
    assert img.end_offset == len(good)
    assert img.nrecords == 2


def test_parse_stops_at_torn_final_line():
    raw = _log({"t": "begin", "e": 0},
               {"t": "done", "e": 0, "k": "mkdir", "p": ["d"]})
    img = SpillImage.parse(raw + b'{"t":"done","e":0')   # no newline
    assert img.durable_dirs == {"d"}
    assert img.end_offset == len(raw)


def test_parse_last_begin_wins():
    """Records of a rolled-back attempt (earlier epoch) must never
    resurrect: a later ``begin`` supersedes everything before it."""
    raw = _log({"t": "begin", "e": 0},
               {"t": "done", "e": 0, "k": "create", "p": ["old.bin"]},
               {"t": "jrnl", "e": 0, "p": "old.bin", "d": 0},
               {"t": "begin", "e": 1},
               {"t": "done", "e": 1, "k": "mkdir", "p": ["new"]})
    img = SpillImage.parse(raw)
    assert img.epoch == 1
    assert img.durable_files == {}
    assert img.journal == {}
    assert img.durable_dirs == {"new"}


def test_parse_epoch_mismatch_stops():
    raw = _log({"t": "begin", "e": 2},
               {"t": "done", "e": 2, "k": "mkdir", "p": ["a"]},
               {"t": "done", "e": 1, "k": "mkdir", "p": ["b"]},  # stale
               {"t": "done", "e": 2, "k": "mkdir", "p": ["c"]})
    img = SpillImage.parse(raw)
    assert img.durable_dirs == {"a"}      # stop at the mismatch, not skip


def test_parse_uncertain_is_admit_minus_settle():
    raw = _log({"t": "begin", "e": 0},
               {"t": "admit", "e": 0, "k": "write", "p": ["f"]},
               {"t": "admit", "e": 0, "k": "write", "p": ["f"]},
               {"t": "done", "e": 0, "k": "write", "p": ["f"],
                "segs": [[0, 1, 0]]},
               {"t": "admit", "e": 0, "k": "remove_tree", "p": ["t"]})
    img = SpillImage.parse(raw)
    assert img.uncertain == {("write", ("f",)): 1,
                             ("remove_tree", ("t",)): 1}
    assert img.removal_uncertain == {"t"}


def test_parse_elided_done_settles_without_claiming():
    raw = _log({"t": "begin", "e": 0},
               {"t": "admit", "e": 0, "k": "mkdir", "p": ["d"]},
               {"t": "done", "e": 0, "k": "mkdir", "p": ["d"], "el": 1})
    img = SpillImage.parse(raw)
    assert img.uncertain == {}
    assert img.durable_dirs == set()      # an elided op proved nothing new


def test_parse_removal_retracts_durable_claims():
    raw = _log({"t": "begin", "e": 0},
               {"t": "done", "e": 0, "k": "mkdir", "p": ["d"]},
               {"t": "done", "e": 0, "k": "create", "p": ["d/f"]},
               {"t": "done", "e": 0, "k": "remove_tree", "p": ["d"]})
    img = SpillImage.parse(raw)
    assert img.durable_dirs == set()
    assert img.durable_files == {}
    assert "d" in img.removed and "d/f" in img.removed


def test_parse_rename_rekeys_journal():
    raw = _log({"t": "begin", "e": 0},
               {"t": "jrnl", "e": 0, "p": "a", "d": 1},
               {"t": "jrnl", "e": 0, "p": "a/f", "d": 0},
               {"t": "jmv", "e": 0, "s": "a", "d": "b"})
    img = SpillImage.parse(raw)
    assert img.journal == {"b": True, "b/f": False}


def test_parse_committed_flag():
    raw = _log({"t": "begin", "e": 0},
               {"t": "committed", "e": 0})
    assert SpillImage.parse(raw).committed


# ---------------------------------------------------------------------------
# diverted-stream assembly + verification
# ---------------------------------------------------------------------------

def _crc(b):
    import zlib
    return zlib.crc32(b) & 0xFFFFFFFF


def test_assemble_later_wins_and_zero_fills():
    assert _assemble([(0, b"abcd"), (2, b"XY")]) == b"abXY"
    assert _assemble([(2, b"zz")]) == b"\x00\x00zz"
    assert _assemble([]) == b""


def test_verify_exact_coverage_required():
    content = b"hello world"
    segs = [[0, 5, _crc(b"hello")], [5, 6, _crc(b" world")]]
    assert _verify(content, segs)
    # a gap in coverage (tail unproven) fails
    assert not _verify(content, segs[:1])
    # crc mismatch (content overwritten since the record) fails
    assert not _verify(b"hellO world", segs)
    # segment overhanging the content fails
    assert not _verify(b"hel", [[0, 5, _crc(b"hello")]])
    # empty content needs no segments
    assert _verify(b"", [])


def test_verify_overlapping_segments_ok_when_crcs_hold():
    content = b"aabb"
    segs = [[0, 4, _crc(b"aabb")], [2, 2, _crc(b"bb")]]
    assert _verify(content, segs)


# ---------------------------------------------------------------------------
# end-to-end: spill lifecycle on a live mount
# ---------------------------------------------------------------------------

def _body(fs):
    fs.mkdir("out")
    fs.write_file("out/a.bin", b"alpha" * 64)
    fs.chmod("out/a.bin", 0o640)
    fs.write_file("out/b.bin", b"beta")
    fs.mkdir("out/sub")
    fs.write_file("out/sub/c.bin", b"gamma" * 16)
    fs.unlink("out/b.bin")


def _data(be):
    snap = be.snapshot()
    return ({p: bytes(d) for p, d in snap["files"].items()
             if not p.startswith(".spill")},
            {d for d in snap["dirs"] if d and not p_spill(d)})


def p_spill(p):
    return p == ".spill" or p.startswith(".spill/")


def _baseline():
    be = InMemoryBackend()
    fs = CannyFS(be, flags=EagerFlags(), echo_errors=False)
    fs.enable_spill(".spill")
    run_transaction(fs, _body)
    fs.close()
    return _data(be)


def test_spill_journal_retired_on_commit():
    be = InMemoryBackend()
    fs = CannyFS(be, flags=EagerFlags(), echo_errors=False)
    fs.enable_spill(".spill")
    with Transaction(fs):
        fs.mkdir("out")
        fs.write_file("out/a.bin", b"x" * 32)
        fs.drain()
        assert be.stat(".spill/journal.log").exists
        assert fs.engine.stats.spill_records > 0
        assert fs.engine.stats.spill_cuts > 0
    # commit retired the log; the marker survives as the committed proof
    assert not be.stat(".spill/journal.log").exists
    assert be.read_at(".spill/CUT", 0, -1).startswith(b"committed:")
    fs.close()


def test_resume_after_full_retirement_reports_committed():
    """Kill after commit retired the journal: the marker proof alone must
    tell a restart the window finished (no doomed from-scratch re-run)."""
    be = InMemoryBackend()
    fs = CannyFS(be, flags=EagerFlags(), echo_errors=False)
    fs.enable_spill(".spill")
    run_transaction(fs, _body)
    fs.close()
    fs2 = CannyFS(be, flags=EagerFlags(), echo_errors=False)
    report = fs2.resume(".spill")
    assert report["committed"]
    assert not report["resumable"]
    fs2.close()


def test_rollback_advances_epoch_no_resurrection():
    """After a rollback, a resume of the same log must see the *new*
    attempt only — the rolled-back epoch's records are dead."""
    be = InMemoryBackend()
    fs = CannyFS(be, flags=EagerFlags(), echo_errors=False)
    fs.enable_spill(".spill")
    txn = Transaction(fs)
    with pytest.raises(RuntimeError):
        with txn:
            fs.mkdir("old")
            fs.write_file("old/x.bin", b"dead")
            fs.drain()
            raise RuntimeError("boom")     # __exit__ rolls back
    assert txn.rolled_back
    raw = be.read_at(".spill/journal.log", 0, -1)
    img = SpillImage.parse(raw)
    assert not img.began          # cut flushed, but no begin in new epoch
    assert img.durable_files == {} and img.journal == {}
    fs.close()


def test_kill_resume_converges_and_elides():
    baseline = _baseline()

    be = InMemoryBackend()
    plan = FaultPlan([FaultRule(ops=("write", "write_vec"),
                                path_glob="out/sub/*", outcome="kill",
                                max_failures=1)], seed=3)
    fb = FaultInjectingBackend(be, plan)
    fs = CannyFS(fb, flags=EagerFlags(flush=False), echo_errors=False)
    fs.enable_spill(".spill")
    with pytest.raises(ProcessKilled):
        run_transaction(fs, _body, retries=3)
    assert plan.kills == 1
    assert fs.engine.stats.rollbacks == 0     # preemption, not failure
    try:
        fs.close()
    except Exception:
        pass

    fb.revive()
    fs2 = CannyFS(fb, flags=EagerFlags(flush=False), echo_errors=False)
    report = fs2.resume(".spill")
    assert report["resumable"]
    assert report["records"] > 0
    run_transaction(fs2, _body)
    fs2.close()
    assert fs2.engine.stats.resumes == 1
    # the proven prefix (out/, a.bin, …) was elided, not redone
    assert fs2.engine.stats.resume_elided_ops > 0
    assert _data(be) == baseline
    # commit retired the spill artifacts
    assert not be.stat(".spill/journal.log").exists


def test_resume_on_empty_spill_is_fresh_start():
    be = InMemoryBackend()
    fs = CannyFS(be, flags=EagerFlags(), echo_errors=False)
    report = fs.resume(".spill")
    assert not report["resumable"]
    run_transaction(fs, _body)
    fs.close()
    assert _data(be) == _baseline()


def _forge_spill(be, *recs):
    """Plant a spill log directly on the backend — the state a killed
    process leaves behind, without racing a live engine to produce it."""
    be.mkdir(".spill")
    be.create(".spill/journal.log")
    raw = _log(*recs)
    be.write_at(".spill/journal.log", 0, raw)
    return raw


def test_diverted_stream_mismatch_falls_back_to_rewrite():
    """Recorded segment checksums that do not prove the re-run's stream
    (the interrupted run wrote different bytes, or only a partial record
    survived the kill) force a real rewrite, never an elision."""
    content = b"alpha" * 64
    stale = b"old-bytes"
    be = InMemoryBackend()
    be.mkdir("out")
    be.create("out/a.bin")
    be.write_at("out/a.bin", 0, stale)
    _forge_spill(
        be,
        {"t": "begin", "e": 0},
        {"t": "done", "e": 0, "k": "mkdir", "p": ["out"]},
        {"t": "jrnl", "e": 0, "p": "out", "d": 1},
        {"t": "done", "e": 0, "k": "create", "p": ["out/a.bin"]},
        {"t": "jrnl", "e": 0, "p": "out/a.bin", "d": 0},
        # the record proves only the stale bytes — not the re-run's stream
        {"t": "done", "e": 0, "k": "write", "p": ["out/a.bin"],
         "segs": [[0, len(stale), _crc(stale)]]})

    fs = CannyFS(be, flags=EagerFlags(flush=False), echo_errors=False)
    report = fs.resume(".spill")
    assert report["resumable"]
    with Transaction(fs):
        fs.mkdir("out")
        fs.write_file("out/a.bin", content)
    fs.close()
    assert be.read_at("out/a.bin", 0, -1) == content


def test_diverted_stream_match_is_elided():
    """The happy twin: backend content matches the recorded checksums, so
    the whole create+write stream is elided."""
    content = b"alpha" * 64
    be = InMemoryBackend()
    be.mkdir("out")
    be.create("out/a.bin")
    be.write_at("out/a.bin", 0, content)
    _forge_spill(
        be,
        {"t": "begin", "e": 0},
        {"t": "done", "e": 0, "k": "mkdir", "p": ["out"]},
        {"t": "jrnl", "e": 0, "p": "out", "d": 1},
        {"t": "done", "e": 0, "k": "create", "p": ["out/a.bin"]},
        {"t": "jrnl", "e": 0, "p": "out/a.bin", "d": 0},
        {"t": "done", "e": 0, "k": "write", "p": ["out/a.bin"],
         "segs": [[0, len(content), _crc(content)]]})

    fs = CannyFS(be, flags=EagerFlags(flush=False), echo_errors=False)
    fs.resume(".spill")
    before = be.snapshot()["files"]["out/a.bin"]
    with Transaction(fs):
        fs.mkdir("out")
        fs.write_file("out/a.bin", content)
    fs.close()
    assert fs.engine.stats.resume_elided_ops >= 3   # mkdir + create + write
    assert be.read_at("out/a.bin", 0, -1) == bytes(before)


def test_diverted_stream_of_read_only_views_is_elided():
    """The re-run writes its stream as borrowed read-only slices (as the
    checkpoint manager does): they are buffered, assembled and verified
    against the recorded checksums like bytes, and elided."""
    content = b"alpha" * 64
    be = InMemoryBackend()
    be.mkdir("out")
    be.create("out/a.bin")
    be.write_at("out/a.bin", 0, content)
    _forge_spill(
        be,
        {"t": "begin", "e": 0},
        {"t": "done", "e": 0, "k": "mkdir", "p": ["out"]},
        {"t": "jrnl", "e": 0, "p": "out", "d": 1},
        {"t": "done", "e": 0, "k": "create", "p": ["out/a.bin"]},
        {"t": "jrnl", "e": 0, "p": "out/a.bin", "d": 0},
        {"t": "done", "e": 0, "k": "write", "p": ["out/a.bin"],
         "segs": [[0, len(content), _crc(content)]]})

    fs = CannyFS(be, flags=EagerFlags(flush=False), echo_errors=False)
    fs.resume(".spill")
    view = memoryview(content)
    with Transaction(fs):
        fs.mkdir("out")
        with fs.open("out/a.bin", "wb") as f:
            for lo in range(0, len(view), 100):
                f.write(view[lo:lo + 100])
    fs.close()
    assert fs.engine.stats.resume_elided_ops >= 3
    assert fs.engine.stats.write_copied_bytes == 0
    assert be.read_at("out/a.bin", 0, -1) == content


_VIEWED = bytes(range(256)) * 8


def _view_body(fs):
    """``_body``'s shape with a file streamed from read-only slices."""
    fs.mkdir("out")
    view = memoryview(_VIEWED)
    with fs.open("out/v.bin", "wb") as f:
        for lo in range(0, len(view), 256):
            f.write(view[lo:lo + 256])
    fs.mkdir("out/sub")
    fs.write_file("out/sub/c.bin", b"gamma" * 16)


def test_kill_resume_of_a_view_stream_converges():
    be = InMemoryBackend()
    plan = FaultPlan([FaultRule(ops=("write", "write_vec"),
                                path_glob="out/sub/*", outcome="kill",
                                max_failures=1)], seed=3)
    fb = FaultInjectingBackend(be, plan)
    fs = CannyFS(fb, flags=EagerFlags(flush=False), echo_errors=False)
    fs.enable_spill(".spill")
    with pytest.raises(ProcessKilled):
        run_transaction(fs, _view_body, retries=3)
    assert plan.kills == 1
    try:
        fs.close()
    except Exception:
        pass

    fb.revive()
    fs2 = CannyFS(fb, flags=EagerFlags(flush=False), echo_errors=False)
    assert fs2.resume(".spill")["resumable"]
    run_transaction(fs2, _view_body)
    fs2.close()
    assert fs2.engine.stats.resume_elided_ops > 0
    assert fs2.engine.stats.write_copied_bytes == 0
    files, dirs = _data(be)
    assert files == {"out/v.bin": _VIEWED, "out/sub/c.bin": b"gamma" * 16}
    assert dirs == {"out", "out/sub"}
    assert not be.stat(".spill/journal.log").exists


def test_stale_tail_truncated_on_load():
    """Bytes past the last parsable record (a torn chunk) are physically
    truncated at load so the resumed epoch appends to a clean prefix."""
    be = InMemoryBackend()
    raw = _forge_spill(be,
                       {"t": "begin", "e": 0},
                       {"t": "done", "e": 0, "k": "mkdir", "p": ["out"]},
                       {"t": "jrnl", "e": 0, "p": "out", "d": 1})
    be.write_at(".spill/journal.log", len(raw), b'{"torn')   # no newline

    fs = CannyFS(be, flags=EagerFlags(), echo_errors=False)
    report = fs.resume(".spill")
    assert report["resumable"]
    assert be.read_at(".spill/journal.log", 0, -1) == raw
    fs.close()


def test_rolledback_tombstone_kills_the_window():
    """A log whose last lifecycle record is the rollback tombstone proves
    no window: resume must not trust any of the epoch's claims."""
    be = InMemoryBackend()
    _forge_spill(be,
                 {"t": "begin", "e": 0},
                 {"t": "done", "e": 0, "k": "mkdir", "p": ["out"]},
                 {"t": "jrnl", "e": 0, "p": "out", "d": 1},
                 {"t": "rolledback", "e": 0})
    fs = CannyFS(be, flags=EagerFlags(), echo_errors=False)
    report = fs.resume(".spill")
    assert not report["resumable"]
    assert report["journal_paths"] == 0
    fs.close()


def test_repair_never_journals_preexisting_file():
    """A write_at to a file that pre-dated the transaction, in flight at
    the kill, looks like a landed-but-unjournaled create — except for the
    probe record proving the path existed before the op.  Repair must
    leave it unjournaled, so a rollback of the resumed attempt can never
    unlink pre-transaction user data."""
    for probe_rec in ([{"t": "pre", "e": 0, "p": "user.dat", "x": 1}],
                      []):       # existence unknown: equally off-limits
        be = InMemoryBackend()
        be.create("user.dat")
        be.write_at("user.dat", 0, b"precious")
        _forge_spill(be,
                     {"t": "begin", "e": 0},
                     *probe_rec,
                     {"t": "admit", "e": 0, "k": "write", "p": ["user.dat"]})
        fs = CannyFS(be, flags=EagerFlags(flush=False), echo_errors=False)
        report = fs.resume(".spill")
        assert report["resumable"]
        txn = Transaction(fs)
        with pytest.raises(RuntimeError):
            with txn:
                raise RuntimeError("abort the resumed attempt")
        assert txn.rolled_back
        assert be.read_at("user.dat", 0, -1) == b"precious"
        fs.close()


def test_repair_journals_landed_create_with_absence_proof():
    """The dual: a surviving probe record proving pre-op absence makes
    the landed-but-unjournaled create this window's own — repair
    journals it, and rollback removes it instead of leaking it."""
    be = InMemoryBackend()
    be.create("out.bin")
    be.write_at("out.bin", 0, b"window output")
    _forge_spill(be,
                 {"t": "begin", "e": 0},
                 {"t": "pre", "e": 0, "p": "out.bin", "x": 0},
                 {"t": "admit", "e": 0, "k": "create", "p": ["out.bin"]})
    fs = CannyFS(be, flags=EagerFlags(flush=False), echo_errors=False)
    report = fs.resume(".spill")
    assert report["resumable"] and report["repairs"] >= 1
    txn = Transaction(fs)
    with pytest.raises(RuntimeError):
        with txn:
            raise RuntimeError("abort the resumed attempt")
    assert txn.rolled_back
    assert not be.stat("out.bin").exists
    fs.close()


def test_resumed_mkdir_on_unvouched_dir_surfaces_eexist():
    """Re-execution tolerance is scoped to paths the spill image vouches
    for: a resumed mkdir of a directory the interrupted run never
    reached (it pre-dates the job) must surface the FileExistsError a
    fresh run would, and must not pull the directory into rollback
    scope."""
    be = InMemoryBackend()
    be.mkdir("legacy")           # pre-dates the job; run 1 never saw it
    _forge_spill(be,
                 {"t": "begin", "e": 0},
                 {"t": "done", "e": 0, "k": "mkdir", "p": ["out"]},
                 {"t": "jrnl", "e": 0, "p": "out", "d": 1})
    fs = CannyFS(be, flags=EagerFlags(flush=False), echo_errors=False)
    fs.resume(".spill")
    with pytest.raises(TransactionFailedError):
        with Transaction(fs):
            fs.mkdir("out")      # vouched (journaled): tolerated
            fs.mkdir("legacy")   # unvouched: the genuine error surfaces
    assert be.stat("legacy").exists
    try:
        fs.close()
    except Exception:
        pass


def test_resumed_mkdir_under_window_dir_tolerated():
    """The tolerated side of the scoping: a recordless mkdir that landed
    under a directory this window provably created is the run's own
    output (nothing pre-existing can live below a window-created dir) —
    the re-run's EEXIST is benign and the job commits."""
    be = InMemoryBackend()
    be.mkdir("out")
    be.mkdir("out/sub")          # landed in run 1, record lost to the kill
    _forge_spill(be,
                 {"t": "begin", "e": 0},
                 {"t": "done", "e": 0, "k": "mkdir", "p": ["out"]},
                 {"t": "jrnl", "e": 0, "p": "out", "d": 1})
    fs = CannyFS(be, flags=EagerFlags(flush=False), echo_errors=False)
    fs.resume(".spill")
    with Transaction(fs):
        fs.mkdir("out")          # elided: provably durable
        fs.mkdir("out/sub")      # EEXIST tolerated via the subtree vouch
    fs.close()
    assert be.stat("out/sub").exists


def test_torn_rename_over_existing_keeps_moved_data():
    """Torn COPY+DELETE where the rename target pre-existed and the COPY
    never started: dst holds the stale old content and src the only copy
    of the moved data.  dst-wins would unlink src outright; repair must
    verify dst against src and re-issue the rename instead."""
    be = InMemoryBackend()
    be.create("a.bin")
    be.write_at("a.bin", 0, b"moved payload")
    be.create("b.bin")
    be.write_at("b.bin", 0, b"stale old target")
    _forge_spill(be,
                 {"t": "begin", "e": 0},
                 {"t": "admit", "e": 0, "k": "rename",
                  "p": ["a.bin", "b.bin"]})
    fs = CannyFS(be, flags=EagerFlags(flush=False), echo_errors=False)
    report = fs.resume(".spill")
    assert report["resumable"] and report["repairs"] >= 1
    assert be.read_at("b.bin", 0, -1) == b"moved payload"
    assert not be.stat("a.bin").exists
    fs.close()


def test_overlay_delta_reinstalled_without_walk():
    """Resume replays the proven delta into the overlay: the re-executed
    body's readdir/exists answers come from the reinstalled membership
    delta, and delta_summary shows the claims."""
    be = InMemoryBackend()
    plan = FaultPlan([FaultRule(ops=("write", "write_vec"),
                                path_glob="out/sub/*", outcome="kill",
                                max_failures=1)], seed=3)
    fb = FaultInjectingBackend(be, plan)
    fs = CannyFS(fb, flags=EagerFlags(flush=False), echo_errors=False)
    fs.enable_spill(".spill")
    with pytest.raises(ProcessKilled):
        run_transaction(fs, _body, retries=0)
    try:
        fs.close()
    except Exception:
        pass

    fb.revive()
    fs2 = CannyFS(fb, flags=EagerFlags(flush=False), echo_errors=False)
    fs2.resume(".spill")
    summary = fs2.engine.overlay.delta_summary()
    assert summary["dirs"] > 0
    assert summary["children"] > 0
    assert fs2.exists("out/a.bin")      # answered from the replayed delta
    fs2.close()
