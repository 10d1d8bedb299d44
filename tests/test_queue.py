"""Pull-based work queue: filesystem leases, multi-host drains, takeover.

The contract under test is the ISSUE's acceptance scenario: any number of
hosts lease shards of one shared store, a SIGKILLed host's shard is
adopted after its lease TTL expires, and the merged census stays
byte-identical to an uninterrupted 1-host run — because a lease takeover
is literally the kill/resume path.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from repro.core.lease import (
    Lease,
    LeaseInfo,
    LeaseLost,
    acquire_lease,
    read_lease,
)
from repro.core.sweep import ShardStore, SweepSpec, run_shard, write_merged
from repro.launch.queue import SweepQueue, _shard_done, drain, open_queue

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    for var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS"):
        env.setdefault(var, "1")
    return env


# ----------------------------------------------------------------- leases ---

def test_acquire_is_exclusive_and_released(tmp_path):
    path = str(tmp_path / "s.lease.json")
    a = acquire_lease(path, "a:1:x")
    assert isinstance(a, Lease)
    assert read_lease(path).owner == "a:1:x"
    # a live lease blocks every other acquirer
    assert acquire_lease(path, "b:2:y") is None
    a.release()
    assert read_lease(path) is None
    b = acquire_lease(path, "b:2:y")
    assert b is not None and read_lease(path).owner == "b:2:y"


def test_heartbeat_is_rate_limited_and_refreshes(tmp_path):
    path = str(tmp_path / "s.lease.json")
    lease = acquire_lease(path, "a:1:x", interval=3600.0)
    first = read_lease(path).heartbeat_at
    lease.heartbeat()            # within interval: no rewrite
    assert read_lease(path).heartbeat_at == first
    time.sleep(0.01)
    lease.heartbeat(force=True)  # forced: rewrites now
    assert read_lease(path).heartbeat_at > first


def test_expired_lease_is_broken_and_adopted(tmp_path):
    path = str(tmp_path / "s.lease.json")
    dead = acquire_lease(path, "dead:1:x", ttl=0.05)
    assert dead is not None
    time.sleep(0.1)
    taker = acquire_lease(path, "taker:2:y", ttl=30.0)
    assert taker is not None
    assert read_lease(path).owner == "taker:2:y"
    # the dead owner finds out at its next heartbeat and must stop
    with pytest.raises(LeaseLost):
        dead.heartbeat(force=True)
    # ... and its release must not clobber the new owner's lease
    dead.release()
    assert read_lease(path).owner == "taker:2:y"


def test_torn_lease_file_reads_as_none(tmp_path):
    path = str(tmp_path / "s.lease.json")
    with open(path, "w") as fh:
        fh.write('{"owner": "half')
    assert read_lease(path) is None


def test_lease_info_expiry_math():
    info = LeaseInfo(owner="o", acquired_at=100.0, heartbeat_at=100.0,
                     ttl=30.0)
    assert not info.expired(now=120.0)
    assert info.expired(now=131.0)
    assert info.age(now=110.0) == 10.0


# ------------------------------------------------------- in-process drains ---

def _plan(root, **overrides):
    kwargs = dict(
        name="t",
        families={
            "chain": {"count": 6, "n_matrices": [3, 4], "lo": 24, "hi": 96},
            "bilinear": {"sizes": [32, 64], "per_size": 2},
        },
        n_shards=3,
        backend="cost_model",
        max_measurements=9,
        chunk_size=2,
        save_every=4,
    )
    kwargs.update(overrides)
    spec = SweepSpec(**kwargs)
    spec.save(os.path.join(root, "spec.json"))
    return spec


def test_single_owner_drain_matches_direct_run(tmp_path):
    straight, queued = str(tmp_path / "a"), str(tmp_path / "b")
    os.makedirs(straight), os.makedirs(queued)
    spec = _plan(straight)
    for s in range(spec.n_shards):
        run_shard(spec, straight, s)
    write_merged(spec, straight)

    _plan(queued)
    queue = open_queue(queued)
    assert isinstance(queue, SweepQueue)
    assert drain(queue, "host:1:a", poll=0.01) is True
    queue.merge()
    assert (open(os.path.join(queued, "merged.jsonl")).read()
            == open(os.path.join(straight, "merged.jsonl")).read())
    # every lease was released on the way out
    assert not [f for f in os.listdir(queued) if "lease" in f]


def test_two_owners_interleaved_passes_drain_byte_identically(tmp_path):
    """Two hosts alternating single-pass drains (max_steps pauses shards
    mid-chunk) must converge on the same bytes as one uninterrupted host —
    every handoff exercises the lease-then-resume path."""
    straight, queued = str(tmp_path / "a"), str(tmp_path / "b")
    os.makedirs(straight), os.makedirs(queued)
    spec = _plan(straight)
    for s in range(spec.n_shards):
        run_shard(spec, straight, s)

    _plan(queued)
    queue = open_queue(queued)
    owners = ["hostA:1:x", "hostB:2:y"]
    for round_ in range(200):
        if all(_shard_done(queued, s) for s in range(spec.n_shards)):
            break
        drain(queue, owners[round_ % 2], interval=0.0, max_steps=3)
    else:
        pytest.fail("queue did not drain in 200 interleaved passes")
    for s in range(spec.n_shards):
        name = f"shard-{s:04d}.jsonl"
        assert (open(os.path.join(queued, name)).read()
                == open(os.path.join(straight, name)).read())


def test_drain_skips_foreign_live_lease(tmp_path):
    out = str(tmp_path)
    spec = _plan(out)
    foreign = acquire_lease(ShardStore(out, 0).lease_path, "other:9:z",
                            ttl=3600.0)
    queue = open_queue(out)
    done = drain(queue, "me:1:a", max_steps=10_000)  # single pass
    assert done is False                      # shard 0 still foreign-held
    assert not os.path.exists(ShardStore(out, 0).records_path)
    for s in range(1, spec.n_shards):         # but everything else drained
        assert _shard_done(out, s)
    foreign.release()
    assert drain(queue, "me:1:a", poll=0.01) is True


def test_explain_store_drains_through_queue(tmp_path):
    """The queue auto-detects an explain store and drains it to the same
    bytes as direct shard runs."""
    from repro.explain.runner import (
        ExplainSpec,
        run_explain_shard,
        write_merged_explained,
    )

    census = str(tmp_path / "census")
    os.makedirs(census)
    spec = _plan(census, eff_sigma=0.25, noise_sigma=0.01)
    for s in range(spec.n_shards):
        run_shard(spec, census, s)

    espec = ExplainSpec(census=census, n_shards=2, chunk_size=4,
                        save_every=5, max_measurements=9)
    straight, queued = str(tmp_path / "a"), str(tmp_path / "b")
    for s in range(espec.n_shards):
        run_explain_shard(espec, straight, s)
    write_merged_explained(espec, straight)

    os.makedirs(queued)
    espec.save(os.path.join(queued, "espec.json"))
    queue = open_queue(queued)
    assert queue.kind == "explain"
    assert drain(queue, "host:1:a", poll=0.01) is True
    queue.merge()
    assert (open(os.path.join(queued, "merged.jsonl")).read()
            == open(os.path.join(straight, "merged.jsonl")).read())


# ------------------------------------------------- CLI + SIGKILL takeover ---

#: Enough instances of tens of ms each that a SIGKILL lands while the
#: victim host is mid-shard (mirrors test_sweep.CLI_GRID).
QUEUE_GRID = [
    "--chains", "32", "--chain-sizes", "4,5", "--lo", "24", "--hi", "160",
    "--families", "bilinear", "--sizes", "32,64", "--per-size", "4",
    "--shards", "4", "--max-measurements", "12",
    "--chunk-size", "2", "--save-every", "4",
]


def _cli(module, args, **kwargs):
    cmd = [sys.executable, "-m", f"repro.launch.{module}"] + args
    return subprocess.run(
        cmd, env=_env(), capture_output=True, text=True, timeout=300, **kwargs
    )


def test_cli_sigkill_leased_host_takeover_byte_identical(tmp_path):
    """The acceptance scenario end to end: a host holding leases is
    SIGKILLed mid-chunk; its leases go stale, a second host adopts them
    after TTL expiry, and the merged census is byte-identical to an
    uninterrupted 1-host run."""
    straight, killed = str(tmp_path / "straight"), str(tmp_path / "killed")
    done = _cli("sweep", ["run", "--out", straight, "--workers", "1"]
                + QUEUE_GRID)
    assert done.returncode == 0, done.stderr

    plan = _cli("sweep", ["plan", "--out", killed] + QUEUE_GRID)
    assert plan.returncode == 0, plan.stderr
    victim = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.queue", "work",
         "--out", killed, "--host", "victim",
         "--ttl", "2", "--heartbeat", "0.1", "--poll", "0.1"],
        env=_env(), start_new_session=True,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        # wait until at least one record batch hit disk, then SIGKILL the
        # whole process group mid-census — the lease file stays behind
        deadline = time.time() + 120
        while time.time() < deadline:
            if victim.poll() is not None:
                break
            jsonls = [f for f in os.listdir(killed)
                      if f.endswith(".jsonl")]
            if any(os.path.getsize(os.path.join(killed, f)) > 0
                   for f in jsonls):
                break
            time.sleep(0.005)
        was_running = victim.poll() is None
        os.killpg(victim.pid, signal.SIGKILL)
    finally:
        victim.wait()
    assert was_running, "victim drained the queue before the kill; " \
                        "enlarge QUEUE_GRID"

    # the adopter must wait out the dead lease's TTL, break it, resume the
    # half-done shard, and drain the rest
    adopt = _cli("queue", ["run", "--out", killed, "--hosts", "1",
                           "--ttl", "2", "--heartbeat", "0.2",
                           "--poll", "0.2"])
    assert adopt.returncode == 0, adopt.stderr
    assert "merged" in adopt.stdout

    merged_straight = open(os.path.join(straight, "merged.jsonl")).read()
    merged_killed = open(os.path.join(killed, "merged.jsonl")).read()
    assert merged_killed == merged_straight
    assert merged_straight.count("\n") == 40  # 32 chains + 8 bilinear


def test_cli_two_hosts_drain_byte_identical(tmp_path):
    """Two simulated hosts pulling from one store produce the same bytes
    as a 1-worker run (the CI smoke's local twin, smaller grid)."""
    grid = ["--chains", "8", "--chain-sizes", "3", "--lo", "16", "--hi", "64",
            "--families", "bilinear", "--sizes", "32", "--per-size", "2",
            "--shards", "4", "--max-measurements", "6",
            "--chunk-size", "2", "--save-every", "4"]
    straight, shared = str(tmp_path / "straight"), str(tmp_path / "shared")
    done = _cli("sweep", ["run", "--out", straight, "--workers", "1"] + grid)
    assert done.returncode == 0, done.stderr
    plan = _cli("sweep", ["plan", "--out", shared] + grid)
    assert plan.returncode == 0, plan.stderr
    run = _cli("queue", ["run", "--out", shared, "--hosts", "2",
                         "--poll", "0.1"])
    assert run.returncode == 0, run.stderr
    assert (open(os.path.join(shared, "merged.jsonl")).read()
            == open(os.path.join(straight, "merged.jsonl")).read())


def test_cli_status_reports_leases_and_counts(tmp_path):
    out = str(tmp_path)
    spec = _plan(out)
    run_shard(spec, out, 0)
    holder = acquire_lease(ShardStore(out, 1).lease_path, "probe:7:q")
    assert holder is not None
    status = _cli("queue", ["status", "--out", out])
    assert status.returncode == 0, status.stderr
    assert "sweep queue" in status.stdout
    assert "[done]" in status.stdout          # shard 0 finished
    assert "leased by probe:7:q" in status.stdout
    holder.release()


def test_queue_rejects_unplanned_directory(tmp_path):
    with pytest.raises(SystemExit, match="plan a campaign"):
        open_queue(str(tmp_path))


# --------------------------------------------- manifest-served shard math ---

def test_shard_counts_tail_scans_only_new_bytes(tmp_path):
    """After a manifest commit, shard_counts must serve from the manifest
    watermark plus a tail scan of freshly appended bytes — including a
    torn tail — without reparsing the whole file."""
    from repro.core.sweep import shard_counts

    store = ShardStore(str(tmp_path), 0).open()
    store.append_records([
        {"uid": "a", "index": 0, "family": "chain", "is_anomaly": True},
        {"uid": "b", "index": 1, "family": "chain", "is_anomaly": False},
    ])
    store.write_manifest()
    # records appended after the manifest (a crash window) still count ...
    with open(store.records_path, "a") as fh:
        fh.write(json.dumps({"uid": "c", "index": 2, "family": "bilinear",
                             "is_anomaly": False}) + "\n")
        fh.write('{"uid": "torn", "ind')  # ... and a torn tail is ignored
    counts = shard_counts(ShardStore(str(tmp_path), 0))
    assert counts["done"] == 3
    assert counts["by_family"]["chain"] == {"done": 2, "anomalies": 1}
    assert counts["by_family"]["bilinear"] == {"done": 1, "anomalies": 0}
    assert counts["done_flag"] is False


def test_shard_counts_falls_back_on_legacy_manifest(tmp_path):
    from repro.core.sweep import shard_counts

    store = ShardStore(str(tmp_path), 0).open()
    store.append_records([{"uid": "a", "index": 0, "family": "chain",
                           "is_anomaly": False}])
    # a pre-queue manifest: no records_bytes watermark, no by_family
    with open(store.manifest_path, "w") as fh:
        json.dump({"shard": 0, "n_completed": 1,
                   "completed_uids": ["a"]}, fh)
    counts = shard_counts(ShardStore(str(tmp_path), 0))
    assert counts["done"] == 1
    assert counts["by_family"]["chain"]["done"] == 1


# ------------------------------------------------ one process per device ---

@pytest.mark.parametrize("surface,argv", [
    ("sweep", ["run", "--workers", "2"]),
    ("queue", ["run", "--hosts", "2"]),
])
def test_wall_clock_store_refuses_several_processes(tmp_path, capsys,
                                                    surface, argv):
    """Every wall_clock worker would take the device, which belongs to one
    process: the launcher refuses before it starts a child."""
    from repro.launch import queue as queue_cli
    from repro.launch import sweep as sweep_cli

    out = str(tmp_path)
    SweepSpec(
        name="wc", backend="wall_clock", n_shards=2,
        families={"bilinear": {"sizes": [8], "per_size": 2}},
    ).save(os.path.join(out, "spec.json"))
    main = {"sweep": sweep_cli.main, "queue": queue_cli.main}[surface]
    assert main(argv[:1] + ["--out", out] + argv[1:]) == 2
    assert "one process" in capsys.readouterr().err
    assert not [f for f in os.listdir(out) if f.startswith("shard-")]
