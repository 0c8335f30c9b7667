"""Elastic self-healing training: heartbeat leases, peer watchdog,
agent re-mesh, and the kill-and-rejoin e2e — all driven with injected
host loss (``HYDRAGNN_FAULT_LOSE_HOST_AT_STEP``), not hope.

The e2e starts N=2 single-device CPU processes under per-host
``ElasticAgent`` supervisors, fault-kills one mid-epoch, and asserts the
survivor re-meshes to world 1 WITHOUT operator action, finishes training,
emits a schema-valid ``world_resize`` event with the measured recovery
time, and lands on exactly the trajectory of a clean 1-process restart
from the same rolling checkpoint.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from hydragnn_tpu.train import elastic
from hydragnn_tpu.utils import faults

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _elastic_worker  # noqa: E402

FAST = int(os.getenv("HYDRAGNN_FAST_TEST", "0")) == 1


# ---- coordination primitives ----------------------------------------------


def pytest_heartbeat_writes_and_refreshes_lease(tmp_path):
    path = str(tmp_path / "workers" / "host-0.json")
    hb = elastic.Heartbeat(path, lambda: {"step": 7}, interval_s=0.05)
    hb.start()
    try:
        first = json.load(open(path))
        assert first["step"] == 7 and first["ts"] > 0
        time.sleep(0.2)
        second = json.load(open(path))
        assert second["ts"] > first["ts"]  # the lease refreshes
    finally:
        hb.stop()
    assert not hb._thread.is_alive()


def pytest_dead_members_lease_and_tombstone(tmp_path):
    d = str(tmp_path)
    now = time.time()
    elastic._write_json(elastic._hb_path(d, "worker", 0), {"ts": now})
    elastic._write_json(elastic._hb_path(d, "worker", 1), {"ts": now - 60})
    elastic.write_tombstone(d, 2, reason="preempted", by=2)
    # host 3 never heartbeat: still bootstrapping, NOT dead
    dead = elastic.dead_members(d, [0, 1, 2, 3], lease_s=5.0, kind="worker")
    assert 0 not in dead and 3 not in dead
    assert 1 in dead and 2 in dead
    # tombstones are first-write-wins: the detection ts must not move
    ts = elastic.read_tombstone(d, 2)["ts"]
    elastic.write_tombstone(d, 2, reason="other", by=0)
    assert elastic.read_tombstone(d, 2)["ts"] == ts
    # a CLEANLY finished member (final lease marked done=True) is never
    # dead no matter how stale — end of run, not a loss; rank 0's
    # post-training tail must not be watchdog-killed by finished peers
    elastic._write_json(
        elastic._hb_path(d, "worker", 4), {"ts": now - 3600, "done": True}
    )
    dead = elastic.dead_members(d, [4], lease_s=5.0, kind="worker")
    assert dead == {}
    # a stale lease from an EARLIER generation reads as "respawned worker
    # still booting", not dead (leases persist at one path across
    # re-meshes); the same stale lease IS dead once it names the current
    # generation, and a lease with no gen field counts as current
    elastic._write_json(
        elastic._hb_path(d, "worker", 5), {"ts": now - 60, "gen": 0}
    )
    assert elastic.dead_members(
        d, [5], lease_s=5.0, kind="worker", current_gen=1
    ) == {}
    assert 5 in elastic.dead_members(
        d, [5], lease_s=5.0, kind="worker", current_gen=0
    )
    assert 1 in elastic.dead_members(
        d, [1], lease_s=5.0, kind="worker", current_gen=3
    )  # host 1's lease above has no gen field -> judged as current


def pytest_watchdog_detects_stale_peer_and_self_eviction(tmp_path):
    d = str(tmp_path)
    now = time.time()
    elastic._write_json(elastic._hb_path(d, "worker", 1), {"ts": now - 60})
    losses, evictions = [], []
    wd = elastic.PeerWatchdog(
        d, host=0, members=[0, 1], lease_s=1.0, interval_s=0.05,
        on_loss=losses.append, on_evicted=lambda: evictions.append(1),
    )
    wd.start()
    try:
        deadline = time.time() + 5
        while not losses and time.time() < deadline:
            time.sleep(0.02)
    finally:
        wd.stop()
    assert losses and 1 in losses[0]

    # a host finding its OWN tombstone evicts itself (no split brain)
    elastic.write_tombstone(d, 5, reason="lease_expired", by=0)
    wd2 = elastic.PeerWatchdog(
        d, host=5, members=[5, 6], lease_s=30.0, interval_s=0.05,
        on_loss=losses.append, on_evicted=lambda: evictions.append(1),
    )
    wd2.start()
    try:
        deadline = time.time() + 5
        while not evictions and time.time() < deadline:
            time.sleep(0.02)
    finally:
        wd2.stop()
    assert evictions


def pytest_world_resize_event_and_gauges(tmp_path):
    from hydragnn_tpu.obs import runtime as obs
    from hydragnn_tpu.obs.events import validate_events

    t = obs.RunTelemetry("t", str(tmp_path))
    obs.activate(t)
    try:
        obs.world_resized(old_world=4, new_world=3, gen=2, recovery_s=1.25)
        snap = t.metrics.snapshot()
        assert snap["world_size"] == 3.0
        assert snap["last_recovery_seconds"] == 1.25
    finally:
        obs.deactivate()
    recs = validate_events(
        str(tmp_path / "events.jsonl"), require=["world_resize"]
    )
    ev = [r for r in recs if r["event"] == "world_resize"][0]
    assert ev["old_world"] == 4 and ev["new_world"] == 3
    assert ev["gen"] == 2 and ev["recovery_s"] == 1.25


# ---- fault injection -------------------------------------------------------


def pytest_slow_step_spec(monkeypatch):
    sleeps = []
    monkeypatch.setattr(time, "sleep", sleeps.append)
    monkeypatch.setenv("HYDRAGNN_FAULT_SLOW_STEP", "4:6@0.3")
    for s in range(8):
        faults.slow_step(s)
    assert sleeps == [0.3, 0.3]  # steps 4 and 5 only
    monkeypatch.setenv("HYDRAGNN_FAULT_SLOW_STEP", "2")  # default delay
    faults.slow_step(2)
    assert sleeps[-1] == 0.25


def pytest_lose_host_targets_one_rank_only(monkeypatch):
    # this process is rank 0; a spec naming rank 3 must be a no-op even
    # at the matching step (otherwise the test would have died here)
    monkeypatch.setenv("HYDRAGNN_FAULT_LOSE_HOST_AT_STEP", "3:0")
    faults.lose_host_at_step(0)
    # non-matching step on the matching rank: also a no-op
    monkeypatch.setenv("HYDRAGNN_FAULT_LOSE_HOST_AT_STEP", "0:99")
    faults.lose_host_at_step(0)


@pytest.mark.slow  # subprocess + jax import (~10 s) for one exit code
def pytest_lose_host_kills_targeted_rank():
    code = textwrap.dedent(
        """
        import os
        os.environ["HYDRAGNN_FAULT_LOSE_HOST_AT_STEP"] = "0:2"
        from hydragnn_tpu.utils import faults
        faults.lose_host_at_step(1)
        faults.lose_host_at_step(2)  # exits 113 here
        raise SystemExit(0)
        """
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        timeout=120,
    )
    assert proc.returncode == faults.KILL_EXIT_CODE


def pytest_straggler_shows_in_flight_recorder():
    from hydragnn_tpu.obs.runtime import FlightRecorder

    fr = FlightRecorder(capacity=16, stall_factor=4.0, min_fill=4)
    stalls = []
    for i in range(12):
        t0 = time.perf_counter()
        faults.slow_step(i)  # no env set: free
        dt = time.perf_counter() - t0 + 0.01
        if i == 10:
            dt += 0.5  # the injected straggler's extra wall time
        s = fr.record(dt)
        if s:
            stalls.append(s)
    assert len(stalls) == 1 and stalls[0]["step"] == 10


# ---- agent re-mesh without jax (stub workers) ------------------------------


_STUB_WORKER = textwrap.dedent(
    """
    import json, os, sys, time

    sys.path.insert(0, {root!r})
    from hydragnn_tpu.train import elastic

    coord = os.environ["HYDRAGNN_ELASTIC_DIR"]
    host = int(os.environ["HYDRAGNN_ELASTIC_HOST"])
    gen = int(os.environ["HYDRAGNN_ELASTIC_GEN"])
    members = [int(m) for m in os.environ["HYDRAGNN_ELASTIC_MEMBERS"].split(",")]
    out = os.environ["STUB_OUT"]

    rec = dict(host=host, gen=gen, members=members,
               rank=members.index(host), world=len(members),
               coordinator=os.environ["HYDRAGNN_TPU_COORDINATOR"],
               num=os.environ["HYDRAGNN_TPU_NUM_PROCESSES"],
               pid=os.environ["HYDRAGNN_TPU_PROCESS_ID"],
               detect=os.environ.get("HYDRAGNN_ELASTIC_DETECT_TS"),
               prev=os.environ.get("HYDRAGNN_ELASTIC_PREV_WORLD"))
    with open(os.path.join(out, f"gen{{gen}}-host{{host}}.json"), "w") as f:
        json.dump(rec, f)

    if gen == 0 and host == 2:
        raise SystemExit(113)  # preempted (faults.KILL_EXIT_CODE)
    if gen == 0:
        # survivors: wait for the dying host's tombstone, then exit for
        # re-mesh exactly as the real watchdog would
        deadline = time.time() + 30
        while time.time() < deadline:
            if elastic.read_tombstone(coord, 2) is not None:
                raise SystemExit(elastic.EXIT_RESHAPE)
            time.sleep(0.05)
        raise SystemExit(7)
    raise SystemExit(0)  # gen 1: done
    """
)


@pytest.mark.slow  # subprocess agents; the CI elastic smoke covers 2->1
@pytest.mark.skipif(FAST, reason="subprocess agents — full tier only")
def pytest_agents_remesh_3_to_2_with_stub_workers(tmp_path):
    """Three agents, host 2's worker 'preempted' at gen 0: the survivors
    must re-form as a 2-member gen-1 world with ranks reassigned, the new
    coordinator port, and the detection timestamp carried over — all via
    the shared directory, no agent-to-agent channel."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    stub = tmp_path / "stub_worker.py"
    stub.write_text(_STUB_WORKER.format(root=root))
    out = tmp_path / "out"
    out.mkdir()
    coord = str(tmp_path / "coord")

    env = {**os.environ, "STUB_OUT": str(out)}
    procs = [
        subprocess.Popen(
            [
                sys.executable, "-m", "hydragnn_tpu.train.elastic",
                "--dir", coord, "--host", str(h), "--hosts", "3",
                "--base-port", "23001", "--heartbeat", "0.1",
                "--lease", "1.0",
                "--", sys.executable, str(stub),
            ],
            env=env, cwd=root,
        )
        for h in range(3)
    ]
    try:
        rcs = [p.wait(timeout=120) for p in procs]
    finally:  # expiry fails this case by name; it leaves no agent behind
        for p in procs:
            if p.poll() is None:
                p.kill()
    assert rcs[2] == faults.KILL_EXIT_CODE  # the preempted host's agent
    assert rcs[0] == 0 and rcs[1] == 0  # survivors finished gen 1

    g0h0 = json.load(open(out / "gen0-host0.json"))
    assert g0h0["members"] == [0, 1, 2] and g0h0["world"] == 3
    assert g0h0["coordinator"].endswith(":23001")
    g1h0 = json.load(open(out / "gen1-host0.json"))
    g1h1 = json.load(open(out / "gen1-host1.json"))
    # ranks reassigned over the survivors, fresh coordinator port, and
    # the resize context (detection ts + previous world) passed through
    assert g1h0["members"] == [0, 1] and g1h1["members"] == [0, 1]
    assert (g1h0["rank"], g1h1["rank"]) == (0, 1)
    assert (g1h0["num"], g1h1["num"]) == ("2", "2")
    assert g1h0["coordinator"].endswith(":23002")
    assert g1h0["detect"] is not None and g1h0["prev"] == "3"
    # the gen-1 file records the transition
    gen, info = elastic.latest_gen(coord)
    assert gen == 1
    assert info["members"] == [0, 1]
    assert info["prev_members"] == [0, 1, 2]
    assert info["detect_ts"] is not None


# ---- kill-and-rejoin e2e ---------------------------------------------------


def _meta_of(path_pk):
    from hydragnn_tpu.train import checkpoint as ck

    return ck.pop_train_meta(
        ck._parse_checkpoint_bytes(open(path_pk, "rb").read(), path_pk)
    )


@pytest.mark.slow  # ~90 s multi-process e2e; tier-1's wall budget is
# protected by the dedicated CI "Elastic kill-and-rejoin smoke" step,
# which runs the same scenario (tests/_elastic_smoke.py) before tier-1
@pytest.mark.skipif(FAST, reason="multi-process e2e — full tier only")
def pytest_elastic_kill_and_rejoin_matches_clean_restart(tmp_path):
    """The acceptance e2e: 2 processes, one fault-killed mid-epoch-2. The
    survivor re-meshes to world 1 and finishes all epochs without any
    operator action; a schema-valid ``world_resize`` event records the
    recovery time; the post-resize trajectory is bitwise-identical to a
    clean 1-process restart from the same rolling checkpoint."""
    from hydragnn_tpu.obs.events import validate_events
    from hydragnn_tpu.train.checkpoint import rolling_checkpoints

    workdir = str(tmp_path / "elastic")
    os.makedirs(workdir)
    num_epoch = _elastic_worker.NUM_EPOCH
    # 2 steps/epoch/rank at world 2: rank 1's step 3 is mid-epoch-1. The
    # survivor keeps training (slowed to 0.3 s/step so the lease watchdog
    # always wins the race against run completion) until its watchdog
    # declares the loss; the exact epoch it then resumes from depends on
    # detection latency, so the assertions pin the INVARIANTS: resumed
    # strictly after the first checkpoint, strictly before the end, and
    # ran exactly the remaining epochs.
    rcs = _elastic_worker.run_elastic(
        workdir, n_hosts=2,
        extra_env={
            "HYDRAGNN_FAULT_LOSE_HOST_AT_STEP": "1:3",
            "HYDRAGNN_FAULT_SLOW_STEP": "0:@0.3",
        },
    )
    assert rcs[1] == faults.KILL_EXIT_CODE, rcs
    assert rcs[0] == 0, rcs

    got = json.load(open(os.path.join(workdir, "result.json")))
    assert got["world"] == 1 and got["gen"] >= 1
    resumed = got["resumed_from_epoch"]
    assert resumed is not None and 1 <= resumed < num_epoch, got
    assert got["epochs_run"] == list(range(resumed, num_epoch)), got

    # the event stream (appended across generations) is schema-valid and
    # records the loss + the resize with a real recovery time
    recs = validate_events(
        os.path.join(workdir, "logs", "elastic", "events.jsonl"),
        require=["host_lost", "world_resize", "checkpoint_saved"],
    )
    resize = [r for r in recs if r["event"] == "world_resize"][-1]
    assert resize["old_world"] == 2 and resize["new_world"] == 1
    assert resize["gen"] == got["gen"]
    assert 0.0 < resize["recovery_s"] < 300.0
    lost = [r for r in recs if r["event"] == "host_lost"][0]
    assert lost["host"] == 1
    # async checkpointing was live: saves carry the overlap split
    async_saves = [
        r for r in recs
        if r["event"] == "checkpoint_saved" and r.get("async")
    ]
    assert async_saves, "no async checkpoint_saved events"
    assert all(
        "snapshot_s" in r and "write_s" in r for r in async_saves
    )

    # trajectory check: a CLEAN 1-process restart from the very rolling
    # checkpoint the resized world resumed from must land on the
    # identical final state
    logs = os.path.join(workdir, "logs")
    roll_by_epoch = {
        int(_meta_of(p)["epoch"]): p
        for p in rolling_checkpoints("elastic", path=logs)
    }
    refdir = str(tmp_path / "ref")
    ref_ck = os.path.join(refdir, "logs", "elastic")
    os.makedirs(ref_ck)
    with open(roll_by_epoch[resumed - 1], "rb") as src, open(
        os.path.join(ref_ck, "elastic.pk"), "wb"
    ) as dst:
        dst.write(src.read())
    env = {
        k: v
        for k, v in os.environ.items()
        if not k.startswith(("HYDRAGNN_FAULT_", "HYDRAGNN_ELASTIC_",
                             "HYDRAGNN_TPU_"))
    }
    worker = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "_elastic_worker.py"
    )
    ref = subprocess.run(
        [sys.executable, worker, "worker", refdir],
        env=env, capture_output=True, text=True, timeout=420,
    )
    assert ref.returncode == 0, ref.stderr[-2000:]
    ref_res = json.load(open(os.path.join(refdir, "result.json")))
    assert ref_res["resumed_from_epoch"] == resumed
    assert ref_res["epochs_run"] == got["epochs_run"]
    assert ref_res["final_lr"] == got["final_lr"]
    np.testing.assert_allclose(
        got["final_params_digest"],
        ref_res["final_params_digest"],
        rtol=0,
        atol=0,
    )
