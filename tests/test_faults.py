"""Chaos hardening: deterministic fault injection, store integrity, fsck.

The contract under test is the ISSUE's acceptance bar: a census drained
under a seeded FaultPlan (torn appends, bitrot, dropped fsyncs, stalls,
kills) either commits records byte-identically or fails LOUDLY into a
state fsck can repair — after which a re-drain merges byte-identical to a
never-faulted run, with zero silently dropped records.
"""

import json
import logging
import os
import threading
import time

import pytest

from repro.core.faults import (
    PLAN_ENV,
    FaultPlan,
    FaultSpec,
    InjectedFault,
    active_plan,
)
from repro.core.lease import (
    LEASE_ABSENT,
    LEASE_CORRUPT,
    LEASE_OK,
    LeaseLost,
    acquire_lease,
    acquire_lease_with_backoff,
    read_lease,
    read_lease_ex,
)
from repro.core.retry import RetryPolicy, with_retries
from repro.core.sweep import (
    LINE_CRC_MISMATCH,
    LINE_LEGACY,
    LINE_OK,
    LINE_UNDECODABLE,
    ShardStore,
    StoreDamaged,
    SweepSpec,
    merge_shards,
    parse_record_line,
    record_crc,
    run_shard,
    scan_damage,
    shard_counts,
    sweep_progress,
    write_merged,
)
from repro.launch.fsck import fsck_store
from repro.launch.queue import drain, open_queue


def _plan_spec(root, **overrides):
    kwargs = dict(
        name="chaos",
        families={"chain": {"count": 6, "n_matrices": [3], "lo": 16, "hi": 48}},
        n_shards=2,
        backend="cost_model",
        max_measurements=9,
        chunk_size=2,
        save_every=4,
    )
    kwargs.update(overrides)
    spec = SweepSpec(**kwargs)
    os.makedirs(root, exist_ok=True)
    spec.save(os.path.join(root, "spec.json"))
    return spec


def _drain_all(spec, root, faults=None):
    for s in range(spec.n_shards):
        run_shard(spec, root, s, faults=faults)


def _reference(tmp_path):
    ref = str(tmp_path / "ref")
    spec = _plan_spec(ref)
    _drain_all(spec, ref)
    return spec, ref, write_merged(spec, ref)


# -------------------------------------------------------------- FaultPlan ---

def test_fault_plan_schedules_on_exact_hit_counts():
    plan = FaultPlan([FaultSpec("store.append", "torn_write", 3)])
    assert plan.due("store.append") == []          # hit 1
    assert plan.due("store.append") == []          # hit 2
    armed = plan.due("store.append")               # hit 3: armed
    assert [f.op for f in armed] == ["torn_write"]
    assert plan.claim(armed[0]) is True
    assert plan.claim(armed[0]) is False           # exactly once
    assert plan.due("store.append") == []          # claimed: never re-arms
    assert plan.fired() == [armed[0].id]


def test_fault_plan_sites_are_independent_counters():
    plan = FaultPlan([
        FaultSpec("store.append", "torn_write", 2),
        FaultSpec("campaign.step", "stall", 1, arg=0.0),
    ])
    assert [f.site for f in plan.due("campaign.step")] == ["campaign.step"]
    assert plan.due("store.append") == []          # append count still 1


def test_fault_plan_claims_are_cross_process_via_scoreboard(tmp_path):
    path = str(tmp_path / "plan.json")
    FaultPlan([FaultSpec("store.append", "torn_write", 1)], seed=3).save(path)
    a, b = FaultPlan.load(path), FaultPlan.load(path)   # two "processes"
    fault_a = a.due("store.append")[0]
    fault_b = b.due("store.append")[0]
    assert a.claim(fault_a) is True
    assert b.claim(fault_b) is False               # a won the O_EXCL create
    assert a.fired() == b.fired() == [fault_a.id]


def test_fault_plan_rng_and_roundtrip_are_deterministic(tmp_path):
    path = str(tmp_path / "plan.json")
    plan = FaultPlan([FaultSpec("store.append", "corrupt_byte", 2, 0.5)],
                     seed=11)
    plan.save(path)
    again = FaultPlan.load(path)
    assert again.to_dict() == plan.to_dict()
    spec = plan.faults[0]
    assert (plan.rng(spec).randrange(10**9)
            == again.rng(again.faults[0]).randrange(10**9))


def test_fault_plan_validates_sites_ops_and_schedule():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultSpec("nowhere", "stall", 1)
    with pytest.raises(ValueError, match="unknown fault op"):
        FaultSpec("store.append", "explode", 1)
    with pytest.raises(ValueError, match="1-based"):
        FaultSpec("store.append", "torn_write", 0)
    with pytest.raises(ValueError, match="duplicate fault id"):
        FaultPlan([FaultSpec("store.append", "stall", 1, id="x"),
                   FaultSpec("store.fsync", "stall", 2, id="x")])


def test_active_plan_loads_from_environment(tmp_path, monkeypatch):
    path = str(tmp_path / "plan.json")
    FaultPlan([FaultSpec("lease.acquire", "io_error", 1)], seed=5).save(path)
    monkeypatch.delenv(PLAN_ENV, raising=False)
    assert active_plan() is None
    monkeypatch.setenv(PLAN_ENV, path)
    plan = active_plan()
    assert plan is not None and plan.seed == 5
    assert plan.state_dir == path + ".fired"       # shared scoreboard
    monkeypatch.delenv(PLAN_ENV)
    assert active_plan() is None


# ------------------------------------------------------------------ retry ---

def test_retry_delays_are_bounded_jittered_and_seeded():
    policy = RetryPolicy(attempts=5, base=0.05, cap=0.3, jitter=0.5)
    d1, d2 = policy.delays(seed="w1"), policy.delays(seed="w1")
    assert d1 == d2                                # same seed, same schedule
    assert policy.delays(seed="w2") != d1          # different worker differs
    assert len(d1) == 4
    for k, d in enumerate(d1):
        lo = min(0.3, 0.05 * 2 ** k)
        assert lo <= d <= lo * 1.5                 # jitter never unbounded


def test_with_retries_recovers_then_propagates_last_error():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    slept = []
    assert with_retries(flaky, policy=RetryPolicy(attempts=3, base=0.01),
                        seed="s", sleep=slept.append) == "ok"
    assert len(calls) == 3 and len(slept) == 2

    def broken():
        raise OSError("permanent")

    with pytest.raises(OSError, match="permanent"):
        with_retries(broken, policy=RetryPolicy(attempts=2, base=0.0),
                     seed="s", sleep=lambda _: None)


# -------------------------------------------------- injected store faults ---

def test_torn_append_crashes_then_resumes_byte_identical(tmp_path):
    _, ref, ref_merged = _reference(tmp_path)
    out = str(tmp_path / "chaos")
    spec = _plan_spec(out)
    plan = FaultPlan([FaultSpec("store.append", "torn_write", 1, 0.4)], seed=1)
    with pytest.raises(InjectedFault, match="torn append"):
        _drain_all(spec, out, faults=plan)
    # the torn batch never committed; resume recovers it exactly
    _drain_all(spec, out, faults=plan)
    assert (open(write_merged(spec, out), "rb").read()
            == open(ref_merged, "rb").read())


def test_dropped_fsync_still_commits_records(tmp_path):
    out = str(tmp_path / "s")
    spec = _plan_spec(out, fsync=True)
    plan = FaultPlan([FaultSpec("store.fsync", "drop_fsync", 1)], seed=2)
    _drain_all(spec, out, faults=plan)
    assert plan.fired()                            # the fsync was skipped...
    prog = sweep_progress(spec, out)
    assert prog["completed"] == prog["instances"]  # ...but the data is whole
    assert prog["damaged"] == 0


def test_transient_io_error_on_acquire_is_retried_away(tmp_path):
    path = str(tmp_path / "s.lease.json")
    plan = FaultPlan([FaultSpec("lease.acquire", "io_error", 1)], seed=4)
    with pytest.raises(OSError, match="injected io_error"):
        acquire_lease(path, "a:1:x", faults=plan)  # raw path crashes...
    fresh = FaultPlan([FaultSpec("lease.acquire", "io_error", 1)], seed=4)
    lease = acquire_lease_with_backoff(path, "a:1:x", faults=fresh)
    assert lease is not None                       # ...but backoff absorbs it
    assert fresh.fired()                           # the fault did fire
    lease.release()


def test_bitrot_mid_file_fails_loudly_everywhere(tmp_path):
    """One flipped byte in a committed record: the writer refuses, counts
    surface the damage, and merge refuses — nothing is silently dropped."""
    out = str(tmp_path / "s")
    spec = _plan_spec(out)
    _drain_all(spec, out)
    store = ShardStore(out, 0)
    with open(store.records_path, "r+b") as fh:
        fh.seek(5)
        fh.write(b"\x00")
    with pytest.raises(StoreDamaged, match="run fsck"):
        ShardStore(out, 0).open()                  # writer refuses
    scan = ShardStore(out, 0).open(readonly=True)
    assert scan.damaged == [(1, LINE_UNDECODABLE)]  # reader counts
    assert scan_damage(spec.n_shards, out) == {0: [(1, LINE_UNDECODABLE)]}
    # the O(1) manifest fast path cannot see pre-watermark bitrot; once the
    # manifest is gone/stale (the usual post-crash state) the full rescan
    # surfaces the damage in status too
    os.remove(store.manifest_path)
    assert shard_counts(ShardStore(out, 0))["damaged"] >= 1
    assert sweep_progress(spec, out)["damaged"] >= 1
    with pytest.raises(StoreDamaged, match="1 damaged record line"):
        merge_shards(spec, out)                    # merge refuses, with count
    assert merge_shards(spec, out, strict=False)   # escape hatch still exists


def test_checksum_catches_valid_json_with_wrong_payload(tmp_path):
    """Bitrot that still parses as JSON (the satellite's silent-skip bug
    could never see this) is caught by the per-record CRC."""
    rec = {"uid": "u1", "index": 0, "family": "chain", "winner": "a"}
    line = json.dumps(dict(rec, _crc=record_crc(rec)), sort_keys=True,
                      separators=(",", ":")).encode()
    assert parse_record_line(line + b"\n")[1] == LINE_OK
    tampered = line.replace(b'"winner":"a"', b'"winner":"b"')
    assert parse_record_line(tampered + b"\n")[1] == LINE_CRC_MISMATCH
    legacy = json.dumps(rec, sort_keys=True).encode()
    assert parse_record_line(legacy + b"\n")[1] == LINE_LEGACY
    assert parse_record_line(b'{"no": "uid"}\n')[1] == LINE_UNDECODABLE


# ------------------------------------------------------------------- fsck ---

def test_fsck_acceptance_corruption_to_byte_identical_merge(tmp_path):
    """The acceptance chain: torn append + bitrot -> loud refusal -> fsck
    (excise + quarantine + manifest rebuild) -> re-drain -> merge is
    byte-identical to the never-faulted reference."""
    _, ref, ref_merged = _reference(tmp_path)
    out = str(tmp_path / "chaos")
    spec = _plan_spec(out)
    plan = FaultPlan([
        FaultSpec("store.append", "torn_write", 1, 0.4),
        FaultSpec("store.append", "corrupt_byte", 2),
    ], seed=7)
    with pytest.raises(InjectedFault):
        _drain_all(spec, out, faults=plan)
    _drain_all(spec, out, faults=plan)             # resume; bitrot fires
    assert set(plan.fired()) == {f.id for f in plan.faults}
    with pytest.raises(StoreDamaged):
        write_merged(spec, out)

    report = fsck_store(out)
    kinds = {f.kind for f in report.findings}
    assert "mid_file_corruption" in kinds
    assert "manifest_drift" in kinds               # done flag cleared too
    assert report.remaining == 0
    qdir = os.path.join(out, "quarantine")
    assert os.path.exists(os.path.join(qdir, "damage-report.json"))
    quarantined = [f for f in os.listdir(qdir) if ".line-" in f]
    assert quarantined                             # damaged bytes preserved

    assert fsck_store(out).clean                   # idempotent
    _drain_all(spec, out)                          # re-runs ONLY the excised
    assert (open(write_merged(spec, out), "rb").read()
            == open(ref_merged, "rb").read())


def test_fsck_truncates_torn_tail_without_losing_records(tmp_path):
    out = str(tmp_path / "s")
    spec = _plan_spec(out)
    _drain_all(spec, out)
    store = ShardStore(out, 0)
    n_before = len(ShardStore(out, 0).open(readonly=True).records)
    with open(store.records_path, "ab") as fh:
        fh.write(b'{"uid": "half-written')       # kill mid-append
    report = fsck_store(out)
    assert [f.kind for f in report.findings
            if f.shard == 0 and f.kind == "torn_tail"]
    scan = ShardStore(out, 0).open(readonly=True)
    assert len(scan.records) == n_before and not scan.damaged


def test_fsck_rebuilds_drifted_manifest_from_records(tmp_path):
    out = str(tmp_path / "s")
    spec = _plan_spec(out)
    _drain_all(spec, out)
    store = ShardStore(out, 0)
    manifest = json.load(open(store.manifest_path))
    manifest["n_completed"] = 999                 # stale/foreign rewrite
    json.dump(manifest, open(store.manifest_path, "w"))
    report = fsck_store(out)
    assert [f for f in report.findings if f.kind == "manifest_drift"]
    fixed = json.load(open(store.manifest_path))
    assert fixed["n_completed"] == len(ShardStore(out, 0).open().records)
    assert fixed["done"] is True                  # no records lost: done kept


def test_fsck_handles_lease_and_engine_and_tmp_casualties(tmp_path):
    out = str(tmp_path / "s")
    spec = _plan_spec(out)
    store = ShardStore(out, 0)
    os.makedirs(out, exist_ok=True)
    with open(store.lease_path, "w") as fh:
        fh.write('{"owner": "half')               # corrupt lease
    with open(store.engine_path, "w") as fh:
        fh.write("not json")                      # corrupt engine state
    with open(os.path.join(out, "shard-0001.manifest.json.tmp"), "w") as fh:
        fh.write("{}")                            # orphaned atomic rename
    live = acquire_lease(ShardStore(out, 1).lease_path, "alive:1:x",
                         ttl=3600.0)
    report = fsck_store(out)
    kinds = {f.kind for f in report.findings}
    assert {"corrupt_lease", "corrupt_engine_state",
            "leftover_tmp", "live_lease"} <= kinds
    assert not os.path.exists(store.lease_path)   # shard stealable again
    assert not os.path.exists(store.engine_path)
    assert os.path.exists(ShardStore(out, 1).lease_path)  # live: untouched
    assert report.remaining == 1                  # the live-lease skip
    live.release()


def test_fsck_dry_run_reports_but_changes_nothing(tmp_path):
    out = str(tmp_path / "s")
    spec = _plan_spec(out)
    _drain_all(spec, out)
    store = ShardStore(out, 0)
    with open(store.records_path, "r+b") as fh:
        fh.seek(5)
        fh.write(b"\x00")
    before = open(store.records_path, "rb").read()
    report = fsck_store(out, dry_run=True)
    assert report.remaining > 0
    assert [f for f in report.findings if f.action.startswith("would_")]
    assert open(store.records_path, "rb").read() == before
    assert not os.path.exists(os.path.join(out, "quarantine"))


def test_fsck_quarantines_damaged_merged_artifact(tmp_path):
    out = str(tmp_path / "s")
    spec = _plan_spec(out)
    _drain_all(spec, out)
    write_merged(spec, out)
    merged = os.path.join(out, "merged.jsonl")
    with open(merged, "r+b") as fh:
        fh.seek(3)
        fh.write(b"\xff")
    report = fsck_store(out)
    assert [f for f in report.findings if f.kind == "damaged_merged"]
    assert not os.path.exists(merged)             # derived data: regenerate
    write_merged(spec, out)                       # regenerates cleanly
    assert fsck_store(out).clean


# -------------------------------------------------------- lease hardening ---

def test_corrupt_lease_reads_as_corrupt_and_is_stolen_with_warning(
        tmp_path, caplog):
    path = str(tmp_path / "s.lease.json")
    assert read_lease_ex(path) == (None, LEASE_ABSENT)
    with open(path, "w") as fh:
        fh.write('{"owner": "half')
    info, state = read_lease_ex(path)
    assert info is None and state == LEASE_CORRUPT
    with caplog.at_level(logging.WARNING, logger="repro.core.lease"):
        lease = acquire_lease(path, "thief:1:x")
    assert lease is not None                       # stale-equivalent: stolen
    assert any("corrupt" in r.message for r in caplog.records)
    info, state = read_lease_ex(path)
    assert state == LEASE_OK and info.owner == "thief:1:x"
    lease.release()


def test_lease_contention_backoff_exactly_one_winner_per_round(tmp_path):
    """N threads race acquire_lease_with_backoff: every round exactly one
    thread wins, the losers back off and return None (satellite c)."""
    path = str(tmp_path / "s.lease.json")
    n_threads, rounds = 8, 3
    for round_ in range(rounds):
        winners, barrier = [], threading.Barrier(n_threads)

        def race(i):
            barrier.wait()
            lease = acquire_lease_with_backoff(
                path, f"host{i}:1:r{round_}", ttl=30.0)
            if lease is not None:
                winners.append(lease)

        threads = [threading.Thread(target=race, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(winners) == 1, f"round {round_}: {len(winners)} winners"
        assert read_lease(path).owner == winners[0].owner
        winners[0].release()


def test_heartbeat_stall_loses_lease_to_takeover(tmp_path):
    """The duplicate-takeover race, scheduled: a heartbeat stall sleeps
    past the TTL, another host steals the shard, and the stalled owner
    gets LeaseLost instead of silently double-writing."""
    path = str(tmp_path / "s.lease.json")
    plan = FaultPlan([FaultSpec("lease.heartbeat", "stall", 1, arg=0.6)],
                     seed=9)
    victim = acquire_lease(path, "victim:1:x", ttl=0.3, faults=plan)
    assert victim is not None
    outcome = {}

    def stalled_beat():
        try:
            victim.heartbeat(force=True)
            outcome["result"] = "beat"
        except LeaseLost:
            outcome["result"] = "lost"

    t = threading.Thread(target=stalled_beat)
    t.start()
    time.sleep(0.45)                               # mid-stall, TTL expired
    thief = acquire_lease(path, "thief:2:y", ttl=30.0)
    assert thief is not None
    t.join()
    assert outcome["result"] == "lost"
    assert read_lease(path).owner == "thief:2:y"
    thief.release()


# --------------------------------------------------------- queue degrades ---

def _shard_done(out, shard):
    manifest = ShardStore(out, shard).read_manifest()
    return bool(manifest and manifest.get("done"))


def test_drain_skips_damaged_shard_and_recovers_after_fsck(tmp_path):
    out = str(tmp_path)
    spec = _plan_spec(out, n_shards=2)
    run_shard(spec, out, 0)                        # commit some records...
    store = ShardStore(out, 0)
    with open(store.records_path, "r+b") as fh:
        fh.seek(5)
        fh.write(b"\x00")                          # ...then rot one byte
    os.remove(store.manifest_path)                 # not marked done
    queue = open_queue(out)
    messages = []
    done = drain(queue, "host:1:a", poll=0.01, say=messages.append)
    assert done is False                           # damaged shard remains
    assert any("damaged" in m for m in messages)
    assert any("fsck" in m for m in messages)
    assert _shard_done(out, 1)                     # healthy shard drained
    assert not os.path.exists(store.lease_path)    # lease released, not held
    fsck_store(out)
    assert drain(queue, "host:1:a", poll=0.01) is True
    queue.merge()                                  # no refusal post-fsck


def test_queue_work_exits_nonzero_on_damaged_shards(tmp_path, capsys):
    """A drain that leaves damaged shards behind is a failure the
    operator must see: ``queue work`` exits 1 and names fsck."""
    from repro.launch.queue import main as queue_main

    out = str(tmp_path)
    spec = _plan_spec(out, n_shards=2)
    run_shard(spec, out, 0)
    store = ShardStore(out, 0)
    with open(store.records_path, "r+b") as fh:
        fh.seek(5)
        fh.write(b"\x00")
    os.remove(store.manifest_path)
    assert queue_main(["work", "--out", out, "--poll", "0.01"]) == 1
    captured = capsys.readouterr()
    assert "(damaged)" in captured.out and "fsck" in captured.err
    fsck_store(out)
    assert queue_main(["work", "--out", out, "--poll", "0.01"]) == 0


# ------------------------------------------------- merge crash resilience ---

def test_killed_merge_leaves_no_torn_store_and_reruns_identical(tmp_path):
    """SIGKILL during merge itself (satellite c): merge writes through a
    tmp + atomic rename, so a kill at ANY point leaves either the old
    bytes or the new bytes, never a torn merged.jsonl — simulated
    deterministically by strewing a half-written merge tmp around."""
    out = str(tmp_path / "s")
    spec = _plan_spec(out)
    _drain_all(spec, out)
    merged = write_merged(spec, out)
    good = open(merged, "rb").read()

    # a merge killed mid-write leaves only a torn tmp file
    os.remove(merged)
    with open(merged + ".tmp", "wb") as fh:
        fh.write(good[: len(good) // 2])           # torn half-merge
    report = fsck_store(out)                       # the orphan is swept up
    assert [f for f in report.findings if f.kind == "leftover_tmp"]
    assert not os.path.exists(merged + ".tmp")
    assert write_merged(spec, out) == merged       # re-run merges cleanly
    assert open(merged, "rb").read() == good       # byte-identical

    # re-running without fsck also recovers: the tmp is simply overwritten
    os.remove(merged)
    with open(merged + ".tmp", "wb") as fh:
        fh.write(good[: len(good) // 3])
    assert write_merged(spec, out) == merged
    assert open(merged, "rb").read() == good

    # a kill AFTER the rename but before cleanup: merged is already whole
    assert write_merged(spec, out) == merged
    assert open(merged, "rb").read() == good


def test_committed_final_line_bitrot_is_damage_not_torn_tail(tmp_path):
    """Bitrot on the LAST committed record of a done shard must not pass
    for an uncommitted torn tail: the manifest watermark covers it, so
    readers count it damaged, merge refuses, fsck clears `done`, and the
    queue re-drains the excised instance (regression: this used to strand
    the shard at done/0-records forever)."""
    _, _, ref_merged = _reference(tmp_path)
    good = open(ref_merged, "rb").read()
    root = str(tmp_path / "out")
    spec = _plan_spec(root)
    _drain_all(spec, root)

    # corrupt a byte of the FINAL line of shard 1 (keep its terminator)
    path = os.path.join(root, "shard-0001.jsonl")
    data = bytearray(open(path, "rb").read())
    final_start = data.rindex(b"\n", 0, len(data) - 1) + 1
    data[final_start + 5] ^= 0xFF
    open(path, "wb").write(bytes(data))

    ro = ShardStore(root, 1).open(readonly=True)
    assert ro.damaged, "committed final-line bitrot invisible to readers"
    with pytest.raises(StoreDamaged):
        ShardStore(root, 1).open()
    with pytest.raises(StoreDamaged, match="damaged record line"):
        merge_shards(spec, root)

    report = fsck_store(root)
    assert report.remaining == 0
    assert not ShardStore(root, 1).read_manifest().get("done"), \
        "fsck kept `done` on a shard that lost a committed record"

    # an UNCOMMITTED torn tail (past the watermark) still truncates freely
    with open(os.path.join(root, "shard-0000.jsonl"), "ab") as fh:
        fh.write(b'{"half of an append that never com')
    assert fsck_store(root).clean is False  # torn_tail finding, repaired

    _drain_all(spec, root)
    assert open(write_merged(spec, root), "rb").read() == good


# ------------------------------------------------ CLI chaos soak (scaled) ---

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _cli(module, args, extra_env=None):
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    for var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS"):
        env.setdefault(var, "1")
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "-m", f"repro.launch.{module}"] + args,
        env=env, capture_output=True, text=True, timeout=300,
    )


def test_cli_chaos_drain_fsck_merge_byte_identical(tmp_path):
    """The acceptance soak, scaled down: a 2-host drain under a seeded
    fault plan (SIGKILL + torn append + bitrot + heartbeat stall), passes
    repeated with fsck until drained — merged output byte-identical to the
    fault-free run, every fault on the scoreboard, nothing silently lost."""
    grid = ["--chains", "8", "--chain-sizes", "3", "--lo", "16", "--hi", "64",
            "--families", "bilinear", "--sizes", "32", "--per-size", "2",
            "--shards", "4", "--max-measurements", "6",
            "--chunk-size", "2", "--save-every", "4"]
    straight, chaos = str(tmp_path / "straight"), str(tmp_path / "chaos")
    done = _cli("sweep", ["run", "--out", straight, "--workers", "1"] + grid)
    assert done.returncode == 0, done.stderr
    plan_cmd = _cli("sweep", ["plan", "--out", chaos] + grid)
    assert plan_cmd.returncode == 0, plan_cmd.stderr

    plan_path = str(tmp_path / "faults.json")
    FaultPlan([
        FaultSpec("store.append", "torn_write", 1, 0.5),
        FaultSpec("store.append", "corrupt_byte", 2),
        FaultSpec("campaign.step", "sigkill", 5),
        FaultSpec("lease.heartbeat", "stall", 3, arg=3.0),
    ], seed=2026).save(plan_path)
    chaos_env = {PLAN_ENV: plan_path}

    merged_ok = False
    for _ in range(8):
        fsck = _cli("fsck", ["--out", chaos])
        assert fsck.returncode in (0, 1), fsck.stderr
        res = _cli("queue", ["run", "--out", chaos, "--hosts", "2",
                             "--ttl", "2", "--heartbeat", "0.2",
                             "--poll", "0.1"], extra_env=chaos_env)
        if res.returncode == 0 and "merged" in res.stdout:
            merged_ok = True
            break
    assert merged_ok, f"chaos drain never converged:\n{res.stdout}\n{res.stderr}"

    fired = sorted(os.listdir(plan_path + ".fired"))
    assert len(fired) == 4, f"faults not all delivered: {fired}"
    assert (open(os.path.join(chaos, "merged.jsonl"), "rb").read()
            == open(os.path.join(straight, "merged.jsonl"), "rb").read())
    # final fsck: nothing left to repair (quarantine may hold old damage)
    assert _cli("fsck", ["--out", chaos]).returncode == 0
