"""Process-backend contracts.

1. **Backend equivalence**: worker nodes are real OS processes; every
   dispatch, footprint snapshot, marshalled ``sys_*`` call and
   write-back crosses the wire as binary frames — yet the final host
   object store must be bit-identical to the serial elision, for the
   same seeded random DAGs (waits, stealing, migration, coalescing
   on/off) the threads backend is held to.
2. **Wire accounting**: RunReport grows per-kind frame/byte tables and
   per-process stats; both must be populated on a procs run.
3. **Failure semantics**: a task body raising in a worker process (or
   touching a node outside its shipped footprint) must surface the
   error in the host, with clean shutdown.
"""

import os
import random

import pytest

from repro.core import InOut, Myrmics, Out, Safe, SerialRuntime, task
from test_backend_threads import build_wait_app, pipeline_app, random_program


@task
def p_init(ctx, o: Out, v: Safe):
    o.write(v)


@task
def p_bump(ctx, o: InOut, dv: Safe):
    o.write(o.read() + dv)


@task
def p_env(ctx, o: Out):
    o.write(os.environ.get("JAX_PLATFORMS"))


@pytest.mark.parametrize("nw,levels", [(1, [1]), (2, [1]), (4, [1, 2])])
def test_procs_matches_serial_pipeline(nw, levels):
    sr = SerialRuntime()
    sr.run(pipeline_app)
    rt = Myrmics(n_workers=nw, sched_levels=levels, backend="procs")
    rep = rt.run(pipeline_app)
    assert rt.labelled_storage() == sr.labelled_storage()
    assert rep.tasks_spawned == rep.tasks_done
    assert rep.backend == "procs"


@pytest.mark.parametrize("seed", [0, 3, 5, 9])
@pytest.mark.parametrize("steal,migrate,coalesce", [
    (True, None, True),
    (False, 1, False),
])
def test_procs_random_dags_match_serial_oracle(seed, steal, migrate,
                                               coalesce):
    """Seeded random-DAG equivalence: serial / sim / threads / procs all
    produce the same labelled store for the same program."""
    desc = random_program(random.Random(seed))
    oracle = SerialRuntime()
    oracle.run(build_wait_app(desc))
    expect = oracle.labelled_storage()
    for backend in ("sim", "threads", "procs"):
        rt = Myrmics(n_workers=4, sched_levels=[1, 2], backend=backend,
                     steal=steal, migrate_threshold=migrate,
                     coalesce=coalesce)
        rt.run(build_wait_app(desc))
        assert rt.labelled_storage() == expect, (
            f"{backend} diverged from serial (seed={seed}, steal={steal}, "
            f"migrate={migrate}, coalesce={coalesce})")


@pytest.mark.parametrize("name", [
    "jacobi", "raytrace", "bitonic", "kmeans", "matmul", "barnes_hut"])
def test_procs_runs_every_paper_app(name):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.apps import run_app
    r = run_app(name, 4, "flat", backend="procs")
    assert r.tasks > 0
    assert r.cycles > 0          # wall seconds on real backends


def test_procs_task_error_propagates():
    def boom(c, oid):
        raise PermissionError("task body failed in the worker process")

    def app(ctx, root):
        o = ctx.alloc(8, root, label="o")
        ctx.spawn(boom, [Out(o)])
        yield ctx.wait([InOut(root)])

    rt = Myrmics(n_workers=2, sched_levels=[1], backend="procs")
    with pytest.raises(PermissionError, match="task body failed"):
        rt.run(app)


def test_procs_uncovered_access_raises():
    """A shipped task body touching a node outside its snapshot cover
    must fail exactly like the host-side check would."""
    def thief(c, oid, stolen):
        c.write(oid, 2)
        c.write(stolen, 99)   # Safe arg: not covered by the footprint

    def app(ctx, root):
        a = ctx.alloc(8, root, label="a")
        b = ctx.alloc(8, root, label="b")
        ctx.spawn(thief, [Out(b), Safe(a)])
        yield ctx.wait([InOut(root)])

    rt = Myrmics(n_workers=1, sched_levels=[1], backend="procs")
    with pytest.raises(PermissionError, match="no w-covering argument"):
        rt.run(app)


def test_procs_report_wire_and_proc_stats():
    rt = Myrmics(n_workers=2, sched_levels=[1], backend="procs")
    rep = rt.run(pipeline_app)
    wire = rep.wire_summary()
    assert wire["total_frames"] > 0
    assert wire["total_bytes"] > 0
    assert "x_exec" in wire["per_kind"]
    assert "x_complete" in wire["per_kind"]
    assert wire["frames_per_task"] > 0
    procs = rep.proc_summary()
    assert set(procs) == {"w0", "w1"}
    for st in procs.values():
        assert st["pid"] > 0
        assert st["frames_out"] > 0 and st["frames_in"] > 0
    assert sum(st["tasks"] for st in procs.values()) > 0
    # sim/threads reports keep the fields but empty
    rt2 = Myrmics(n_workers=2, sched_levels=[1])
    rep2 = rt2.run(pipeline_app)
    assert rep2.wire == {} and rep2.procs == {}
    assert rep2.wire_summary()["total_frames"] == 0


def test_procs_rejects_sanitizer():
    with pytest.raises(ValueError, match="shared-memory backend"):
        Myrmics(n_workers=2, sched_levels=[1], backend="procs",
                sanitize=True)


def test_procs_spawn_batch_coalesced_frames():
    """With coalescing on, buffered child spawns ship as one
    sys_spawn_batch frame instead of per-spawn frames."""
    def fan(c, rid):
        for i in range(6):
            o = c.alloc(8, rid, label=f"f{i}")
            c.spawn(lambda cc, oo, i=i: cc.write(oo, i), [Out(o)])

    def app(ctx, root):
        rid = ctx.ralloc(root, 1, label="r")
        ctx.spawn(fan, [InOut(rid)])
        yield ctx.wait([InOut(root)])

    rt = Myrmics(n_workers=2, sched_levels=[1], backend="procs",
                 coalesce=True)
    rep = rt.run(app)
    kinds = rep.wire["per_kind"]
    assert "x_call:sys_spawn_batch" not in kinds  # call frames are x_call
    batch = [k for k in kinds if k == "x_call"]
    assert batch, f"no x_call frames in {sorted(kinds)}"
    assert rt.labelled_storage()["f3"] == 3


@pytest.mark.slow
def test_procs_wall_clock_speedup():
    """The tentpole claim: >=3x wall-clock at 8 worker processes vs 1 on
    a GIL-releasing payload.  Only meaningful with >=8 cores; always
    runs the path, only arms the assertion when the cores exist."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.paper_figs import procs_scaling
    rows = procs_scaling(workers=(1, 8), total_work=2e9, repeats=3)
    top = rows[-1]
    assert top["workers"] == 8
    if (os.cpu_count() or 1) >= 8:
        assert top["gate_armed"]
        assert top["speedup_vs_1w"] >= 3.0
    else:
        assert not top["gate_armed"]


# ---------------------------------------------------------------------------
# failure semantics (PR 10): a dead child process must never hang the host
# ---------------------------------------------------------------------------


def _slow_fanout_app(ctx, root):
    oids = [ctx.alloc(64, root, label=f"o{i}") for i in range(10)]
    for i, o in enumerate(oids):
        def body(c, oo, v=i):
            import time
            time.sleep(0.1)
            c.write(oo, v * 7)
        ctx.spawn(body, [Out(o)])
    yield ctx.wait([InOut(root)])


def _kill_one_child(rt, avoid_parked=True, delay=0.35):
    """SIGKILL one worker process shortly into the run (a thread so the
    host's run() is already inside the substrate when it fires)."""
    import signal
    import threading
    import time

    def assassin():
        time.sleep(delay)
        parked = set()
        if avoid_parked:
            with rt.worker_agent._qlock:
                parked = {w for w, s in rt.worker_agent._parked.items() if s}
        for wid, ch in list(rt.sub._channels.items()):
            if wid not in parked:
                os.kill(ch.proc.pid, signal.SIGKILL)
                return
    t = threading.Thread(target=assassin, daemon=True)
    t.start()
    return t


def test_procs_child_death_fails_fast_without_faults():
    """No faults= armed: a worker process dying mid-run surfaces a
    named WorkerDiedError (pid + last in-flight task) promptly via the
    reader's EOF — never the old indefinite recv hang."""
    import time

    from repro.core.faults import WorkerDiedError

    rt = Myrmics(n_workers=2, sched_levels=[1], backend="procs")
    _kill_one_child(rt, avoid_parked=False)
    t0 = time.time()
    with pytest.raises(WorkerDiedError, match="socket EOF"):
        rt.run(_slow_fanout_app)
    assert time.time() - t0 < 30.0, "EOF detection took implausibly long"


def test_procs_child_death_recovers_with_faults():
    """faults= armed: the same SIGKILL becomes a uniform w_dead event,
    the lost queue and in-flight activation replay on the survivor, and
    the store matches the serial oracle.  (The victim is chosen away
    from the worker hosting the app's parked main generator — a
    child-resident suspended continuation is the documented at-most-once
    hole and fails loudly instead.)"""
    sr = SerialRuntime()
    sr.run(_slow_fanout_app)
    rt = Myrmics(n_workers=2, sched_levels=[1], backend="procs",
                 faults=True)
    _kill_one_child(rt)
    rep = rt.run(_slow_fanout_app)
    fs = rep.fault_summary()
    assert fs["workers_killed"] == 1
    assert fs["detections"].get("worker:eof", 0) + \
        fs["detections"].get("worker:send-error", 0) >= 1
    assert rt.labelled_storage() == sr.labelled_storage()
    from repro.analysis.invariants import check_invariants
    check_invariants(rt)


def test_procs_injected_kill_replays_in_flight_task():
    """Injected kill (no real process death needed for the timer): the
    child is terminated via its channel, its in-flight activation
    replays, results match.  The kill fires only once w1 actually has
    a task in flight — a fixed wall-clock timer races child startup
    (slow fork/import can leave the victim idle at the deadline)."""
    import threading
    import time

    sr = SerialRuntime()
    sr.run(_slow_fanout_app)
    rt = Myrmics(n_workers=2, sched_levels=[1], backend="procs",
                 faults=True)

    def sniper():
        deadline = time.time() + 20.0
        while time.time() < deadline:
            if rt.worker_agent.last_task_of("w1") is not None:
                rt.kill_worker("w1")
                return
            time.sleep(0.01)

    threading.Thread(target=sniper, daemon=True).start()
    rep = rt.run(_slow_fanout_app)
    fs = rep.fault_summary()
    assert fs["workers_killed"] == 1
    assert fs["tasks_replayed"] >= 1
    assert rt.labelled_storage() == sr.labelled_storage()
    assert "w1" in rt.dead_workers


def test_procs_parked_generator_death_fails_loudly():
    """Killing the worker whose child process holds a suspended
    generator is the at-most-once limit: recovery must fail with the
    named error (listing the parked tids), not silently replay the
    continuation's side effects."""
    import time

    from repro.core.faults import WorkerDiedError

    rt = Myrmics(n_workers=2, sched_levels=[1], backend="procs",
                 faults=True)

    def kill_parked_host():
        deadline = time.time() + 10.0
        wid = None
        while time.time() < deadline and wid is None:
            time.sleep(0.05)
            with rt.worker_agent._qlock:
                for w, s in rt.worker_agent._parked.items():
                    if s:
                        wid = w
                        break
        if wid is not None:
            rt.kill_worker(wid)

    import threading
    threading.Thread(target=kill_parked_host, daemon=True).start()
    with pytest.raises(WorkerDiedError, match="suspended task"):
        rt.run(_slow_fanout_app)


def _rmw_chain_app(ctx, root):
    oids = ctx.balloc(64, root, 6, label="r")
    for i, o in enumerate(oids):
        ctx.spawn(lambda c, oo, v=i: c.write(oo, v + 1), [Out(o)])
    for o in oids:
        def rmw(c, oo):
            import time
            # long enough that the sniper's kill lands while the body
            # is still in flight (the torn-write window under test)
            time.sleep(0.3)
            c.write(oo, c.read(oo) * 2 + 1)
        ctx.spawn(rmw, [InOut(o)])
    yield ctx.wait([InOut(root)])


def test_procs_snapshot_restores_torn_inflight_task(tmp_path):
    """snapshot_dir= on the real-process backend: the init round's
    commits land, then the child is killed while a read-modify-write
    activation is in flight — exactly the torn-write window — and its
    object rolls back to the committed value before the replay, so the
    RMW applies exactly once."""
    import threading
    import time

    from repro.core.faults import FaultPlan

    sr = SerialRuntime()
    sr.run(_rmw_chain_app)
    rt = Myrmics(n_workers=2, sched_levels=[1], backend="procs",
                 faults=FaultPlan(snapshot_dir=str(tmp_path)))

    def sniper():
        deadline = time.time() + 20.0
        while time.time() < deadline:
            # wait for an in-flight task on w1 *after* the init round
            # has committed (6 init completions), i.e. an RMW body
            if rt.tasks_done >= 6 and \
                    rt.worker_agent.last_task_of("w1") is not None:
                rt.kill_worker("w1")
                return
            time.sleep(0.005)

    threading.Thread(target=sniper, daemon=True).start()
    rep = rt.run(_rmw_chain_app)
    fs = rep.fault_summary()
    assert fs["workers_killed"] == 1
    assert fs["snapshots_saved"] > 0
    assert fs["snapshots_restored"] >= 1
    assert rt.labelled_storage() == sr.labelled_storage()


def test_procs_children_start_pinned_to_cpu(monkeypatch):
    """Worker processes never initialise an accelerator: they start with
    JAX_PLATFORMS=cpu even when the host's environment lacks it, and the
    host's own environment is left as it was."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)

    def app(ctx, root):
        for o in ctx.balloc(8, root, 2, label="env"):
            ctx.spawn(p_env, o)
        yield ctx.wait([InOut(root)])

    rt = Myrmics(n_workers=2, sched_levels=[1], backend="procs")
    rt.run(app)
    assert rt.labelled_storage() == {"env[0]": "cpu", "env[1]": "cpu"}
    assert "JAX_PLATFORMS" not in os.environ


def test_procs_training_refuses_accelerator_parent(monkeypatch):
    """On a TPU host, procs workers (pinned to the CPU) would quietly
    run the device bodies on the CPU: the training driver refuses by
    name instead of starting any worker."""
    import jax

    from repro.configs import get_config
    from repro.train.orchestrator import (
        DeviceBodiesOnProcsError,
        run_myrmics_training,
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(DeviceBodiesOnProcsError,
                       match="device-owning worker class"):
        run_myrmics_training(get_config("qwen2_0_5b").smoke(), steps=1,
                             backend="procs")
