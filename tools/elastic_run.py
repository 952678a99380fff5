#!/usr/bin/env python
"""elastic_run: drive a supervised CPU gang through kill/hang/preempt
faults and prove it resumes from the newest intact checkpoint.

The operational front door for ``paddle_tpu.resilience.elastic`` (the
gang-level counterpart of tools/chaos_run.py): it launches a real
2-worker training gang under :class:`GangSupervisor`, injects — in ONE
run — a hard ``worker_kill``, a silent ``worker_hang`` (only the
heartbeat watchdog can catch it) and a ``preempt_signal`` (graceful
checkpoint-and-exit via ``resilience.graceful_shutdown``), and asserts
the surviving run's loss trajectory is BITWISE identical to an
unfaulted reference run: elasticity must not change the math.

The worker (``--worker``) is a plain static-path training loop — fc +
SGD on deterministic per-step batches — that beats its heartbeat from
the loop body, checkpoints every step with
``save_checkpoint(async_=True)`` (rank 0), resumes itself via
``load_checkpoint``'s newest-intact fallback, and honors preemption
notices at step boundaries. Faults fire at exact global steps
(``at_step``), and a per-step gang barrier (done-markers + the
published checkpoint) guarantees each fault's resume point is at/after
its step, so one inherited ``PADDLE_TPU_CHAOS`` spec fires each fault
exactly once per drill.

Usage:
    python tools/elastic_run.py                  # the 3-fault drill
    python tools/elastic_run.py --steps 16 --kill-at 4 ...
    python tools/elastic_run.py --budget-drill   # budget exhaustion
    python tools/elastic_run.py --self-test      # both, asserted

``--self-test`` is wired into tier-1 via tests/test_tooling.py; the
per-injector scenarios in tools/chaos_run.py --self-test reuse one
cached drill result via :func:`drill_result`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

THIS_FILE = os.path.abspath(__file__)


def _load_sibling(name):
    """Load a sibling tool (tools/ is not a package) the way
    tests/test_tooling.py does — an importlib spec, not sys.path
    games."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(THIS_FILE), f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the worker ---------------------------------------------------------------


def _batch(step, batch=8, dim=4):
    """Deterministic per-step batch: re-executing a step after a resume
    reproduces the exact bytes the first execution saw."""
    import numpy as np

    rng = np.random.RandomState(1000 + int(step))
    return (rng.randn(batch, dim).astype(np.float32),
            rng.randn(batch, 1).astype(np.float32))


def worker_main(args):
    """One gang member: static-path train loop with heartbeats, async
    per-step checkpoints (rank 0), chaos step hooks and graceful
    preemption. Resumes itself from the newest intact checkpoint."""
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.fluid as fluid
    from paddle_tpu import resilience
    from paddle_tpu.framework import io as fio
    from paddle_tpu.resilience.elastic import ATTEMPT_ENV

    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    nranks = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    shutdown = resilience.graceful_shutdown()
    hb = resilience.Heartbeat.from_env()
    out_path = os.path.join(args.out_dir, f"losses_rank{rank}.jsonl")

    pt.enable_static()
    pt.seed(0)
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.data(name="x", shape=[8, 4])
        y = fluid.data(name="y", shape=[8, 1])
        out = fluid.layers.fc(x, size=1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(out, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor()
    exe.run(startup)
    adapter = resilience.ProgramStateAdapter(prog)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        start = fio.load_checkpoint(args.ckpt_dir, model=adapter) or 0

    def graceful_exit():
        # the boundary checkpoint is the per-step async save: make it
        # durable, then exit the code the supervisor relaunches
        # budget-free
        fio.wait_checkpoints()
        shutdown.exit_preempted()

    def wait_for(want, what):
        deadline = time.monotonic() + args.barrier_timeout
        while not all(os.path.exists(p) for p in want):
            if shutdown.requested:
                graceful_exit()
            if time.monotonic() > deadline:
                print(f"rank {rank}: barrier timeout at {what}",
                      file=sys.stderr)
                sys.exit(3)
            time.sleep(0.005)

    def barrier(step):
        """Gang lockstep: every rank's done-marker for ``step`` plus the
        published ``ckpt_<step>``. A fault fired below therefore always
        resumes at/after its own step, so ``at_step`` specs inherited
        across restarts fire exactly once per drill."""
        want = [os.path.join(args.sync_dir, f"done_{r}_{step}")
                for r in range(nranks)]
        want.append(os.path.join(args.ckpt_dir, f"ckpt_{step}"))
        wait_for(want, f"step {step}")

    # every rank of an attempt resumes from the SAME checkpoint: rank 0
    # publishes one a step, so a rank that came up a step's time later
    # loaded a later one, and the two waited on different steps until the
    # watchdog took the gang down (an attempt the drill does not expect)
    attempt = os.environ.get(ATTEMPT_ENV, "0")
    open(os.path.join(args.sync_dir, f"loaded_{attempt}_{rank}"),
         "w").close()
    wait_for([os.path.join(args.sync_dir, f"loaded_{attempt}_{r}")
              for r in range(nranks)], f"start of attempt {attempt}")

    from paddle_tpu.obs import journal as _journal

    for step in range(start + 1, args.steps + 1):
        hb.beat(step)
        if shutdown.requested:
            graceful_exit()
        if _journal.ACTIVE is not None:
            # per-rank flight record (the supervisor hands each worker
            # PADDLE_TPU_RUN_DIR=<run>/rank_NN): number this record by
            # the TRAINER's global step, so a resumed incarnation
            # continues at its checkpoint step and obs.fleet aligns
            # records across ranks and attempts
            _journal.ACTIVE.sync_step(step)
        xb, yb = _batch(step)
        lv = float(np.asarray(
            exe.run(prog, feed={"x": xb, "y": yb},
                    fetch_list=[loss])[0]))
        if rank == 0:
            fio.save_checkpoint(args.ckpt_dir, step, model=adapter,
                                async_=True)
        if _journal.ACTIVE is not None:
            # make the record durable at the step boundary: a
            # worker_kill (os._exit, no atexit) must not cost this
            # step's line — the fleet aggregate's stall/skew
            # attribution reads exactly these lines
            _journal.ACTIVE.flush()
        with open(out_path, "a", encoding="utf-8") as f:
            f.write(json.dumps({"step": step, "loss": lv,
                                "hex": float(lv).hex()}) + "\n")
        open(os.path.join(args.sync_dir, f"done_{rank}_{step}"),
             "w").close()
        barrier(step)
        resilience.fire_step_chaos(step=step, rank=rank)
    fio.wait_checkpoints()
    return 0


# -- the drill ----------------------------------------------------------------


def _final_losses(out_path):
    """step -> loss hex, LAST occurrence winning: steps re-executed
    after a resume overwrite their first recording."""
    out = {}
    with open(out_path, encoding="utf-8") as f:
        for line in f:
            rec = json.loads(line)
            out[rec["step"]] = rec["hex"]
    return out


def _worker_cmd(steps, ckpt_dir, sync_dir, out_dir, barrier_timeout=60.0):
    return [sys.executable, THIS_FILE, "--worker",
            "--steps", str(steps), "--ckpt-dir", ckpt_dir,
            "--sync-dir", sync_dir, "--out-dir", out_dir,
            "--barrier-timeout", str(barrier_timeout)]


_WORKER_ENV = {
    # fresh worker processes must not grab a TPU, or inherit a chaos
    # spec meant for someone else; their journals skip the background
    # entry-analysis compile — that CPU contention can push a loaded
    # worker's step past the hang watchdog (the drill asserts records,
    # not FLOPs attribution)
    "JAX_PLATFORMS": "cpu",
    "PADDLE_TPU_CHAOS": "",
    "PADDLE_TPU_JOURNAL_FLOPS": "0",
    # lockdep in raise mode: a lock-order cycle in any gang worker
    # (journal, prefetcher, async checkpoint barrier — the paths this
    # drill hammers) crashes that worker and fails the drill's
    # trajectory-identity gate with a PTC004 in its journal
    "PADDLE_TPU_LOCKDEP": "1",
}


def _run_reference(root, steps):
    """Unfaulted single-worker run: the trajectory oracle."""
    import subprocess

    dirs = {n: os.path.join(root, f"ref_{n}") for n in
            ("ckpt", "sync", "out")}
    for d in dirs.values():
        os.makedirs(d)
    env = dict(os.environ)
    env.update(_WORKER_ENV)
    # the un-supervised oracle must not journal into any inherited run
    # dir (the drill's supervised gang writes per-rank subdirs instead)
    env.update({"PADDLE_TRAINER_ID": "0", "PADDLE_TRAINERS_NUM": "1",
                "PADDLE_TPU_RUN_DIR": "", "PADDLE_TPU_RANK": ""})
    r = subprocess.run(
        _worker_cmd(steps, dirs["ckpt"], dirs["sync"], dirs["out"]),
        env=env, capture_output=True, text=True)
    if r.returncode != 0:
        raise AssertionError(
            f"unfaulted reference worker failed ({r.returncode}):\n"
            f"{r.stdout}\n{r.stderr}")
    return _final_losses(os.path.join(dirs["out"], "losses_rank0.jsonl"))


def run_drill(steps=12, kill_at=3, hang_at=6, preempt_at=9,
              keep_root=False, verbose=False):
    """The acceptance drill. Returns a result dict (also cached by
    :func:`drill_result` for chaos_run's per-injector scenarios):

    - a 2-worker gang survives, in ONE supervised run, ``worker_kill``
      (rank 1, exit 9), ``worker_hang`` (rank 1; the watchdog fires) and
      ``preempt_signal`` (rank 0; graceful checkpoint-and-exit 75);
    - each relaunch resumes from the newest intact checkpoint;
    - the final per-step loss trajectory is BITWISE identical to an
      unfaulted reference run;
    - restarts/preemptions/watchdog kills/resume latency land in
      ``resilience.*`` counters and ``elastic.*`` journal events
      (supervisor journal at ``<run>/supervisor``);
    - EVERY rank journals its own flight record into
      ``<run>/rank_NN`` (per-attempt run_start headers, step records
      covering the whole trajectory) — the PR-8 worker-journal
      suppression is gone, multi-writer torn lines are impossible by
      construction.
    """
    from paddle_tpu.obs import fleet as _fleet
    from paddle_tpu.obs import metrics as _metrics
    from paddle_tpu.resilience import GangSupervisor

    assert 1 <= kill_at < hang_at < preempt_at < steps
    root = tempfile.mkdtemp(prefix="pt_elastic_drill_")
    reference = _run_reference(root, steps)

    dirs = {n: os.path.join(root, n)
            for n in ("ckpt", "sync", "out", "logs", "hb", "journal")}
    for d in dirs.values():
        os.makedirs(d)
    chaos = (f"worker_kill:at_step={kill_at},rank=1,code=9;"
             f"worker_hang:at_step={hang_at},rank=1;"
             f"preempt_signal:at_step={preempt_at},rank=0")
    env = dict(_WORKER_ENV)
    env["PADDLE_TPU_CHAOS"] = chaos
    # span tracing on: each rank's journal close exports a per-rank
    # Chrome trace next to its journal — fleet_report's self-test
    # merges them into the pid=rank fleet view off this same drill
    env["PADDLE_TPU_TRACE"] = "1"
    sup = GangSupervisor(
        _worker_cmd(steps, dirs["ckpt"], dirs["sync"], dirs["out"]),
        nprocs=2, env=env, heartbeat_dir=dirs["hb"],
        log_dir=dirs["logs"], ckpt_dir=dirs["ckpt"],
        run_dir=dirs["journal"],
        # 10s watchdog: a worker's beat gap is max(gang step time) —
        # on a small CI box two workers' first-step XLA compiles
        # serialize to ~5s, and a spurious mid-compile "hang" inserts
        # a whole extra attempt into the drill trace. The real
        # worker_hang fires in steady state, so the only cost of the
        # margin is a longer (deterministic) detection wait
        max_restarts=3, hang_timeout_s=10.0, term_grace_s=1.0,
        poll_interval_s=0.02, backoff_s=0.05, max_backoff_s=0.1, seed=0)
    before = {k: _metrics.counter(k).value
              for k in ("resilience.restarts", "resilience.preemptions",
                        "resilience.watchdog_kills")}
    t0 = time.monotonic()
    rc = sup.run()
    wall_s = time.monotonic() - t0

    faulted = _final_losses(os.path.join(dirs["out"],
                                         "losses_rank0.jsonl"))
    kinds = [a["kind"] for a in sup.state["attempts"]]
    counters = {k: _metrics.counter(k).value - before[k]
                for k in before}
    result = {
        "rc": rc, "state": sup.state, "attempt_kinds": kinds,
        "reference": reference, "faulted": faulted,
        "bitwise_match": faulted == reference,
        "counter_deltas": counters,
        "journal_dir": dirs["journal"],
        "supervisor_dir": os.path.join(dirs["journal"],
                                       _fleet.SUPERVISOR_DIR),
        "root": root, "wall_s": wall_s,
    }
    failures = []
    if rc != 0:
        failures.append(f"gang did not complete: rc={rc}")
    if kinds != ["crash", "hang", "preempt", "ok"]:
        failures.append(f"attempt outcomes {kinds} != "
                        "['crash', 'hang', 'preempt', 'ok']")
    crash = sup.state["attempts"][0] if sup.state["attempts"] else {}
    if kinds[:1] == ["crash"] and (crash.get("rank"), crash.get("code")) \
            != (1, 9):
        failures.append(f"worker_kill crash not attributed: {crash}")
    if sup.state["restarts"] != 2:
        failures.append(f"restarts {sup.state['restarts']} != 2 "
                        "(kill + hang; preemption must be budget-free)")
    if sup.state["preemptions"] != 1:
        failures.append(f"preemptions {sup.state['preemptions']} != 1")
    if sup.state["watchdog_kills"] != 1:
        failures.append(
            f"watchdog_kills {sup.state['watchdog_kills']} != 1")
    if set(faulted) != set(range(1, steps + 1)):
        failures.append(f"faulted run covered steps {sorted(faulted)}, "
                        f"want 1..{steps}")
    if faulted != reference:
        bad = [s for s in reference
               if faulted.get(s) != reference[s]][:4]
        failures.append(
            "loss trajectory diverged from the unfaulted reference at "
            f"steps {bad}: elasticity changed the math")
    for name, want in (("resilience.restarts", 2),
                       ("resilience.preemptions", 1),
                       ("resilience.watchdog_kills", 1)):
        if counters[name] != want:
            failures.append(f"{name} delta {counters[name]} != {want}")
    # fleet contract: EVERY attempt's ranks journaled parseable
    # per-rank flight records (no more PR-8 suppression), the union of
    # their step records covers the whole trajectory, and the
    # supervisor's elastic.* events landed in <run>/supervisor
    try:
        n_attempts = len(sup.state["attempts"])
        ranks = _fleet.rank_dirs(dirs["journal"])
        if sorted(ranks) != [0, 1]:
            failures.append(
                f"per-rank journals missing: found ranks "
                f"{sorted(ranks)} under {dirs['journal']}")
        covered = set()
        for r, p in sorted(ranks.items()):
            run = _fleet.load_journal(p)
            if run["parse_errors"]:
                failures.append(f"rank {r} journal has parse errors: "
                                f"{run['parse_errors'][:2]}")
            if len(run["run_starts"]) != n_attempts:
                failures.append(
                    f"rank {r} journaled {len(run['run_starts'])} "
                    f"incarnations != {n_attempts} attempts")
            hdr = run["header"] or {}
            if hdr.get("rank") != r:
                failures.append(f"rank {r} header carries rank "
                                f"{hdr.get('rank')}")
            covered |= {s["step"] for s in run["steps"]
                        if isinstance(s.get("step"), int)}
        if ranks and covered != set(range(1, steps + 1)):
            failures.append(
                f"rank journals cover steps {sorted(covered)}, want "
                f"1..{steps}")
        sup_run = _fleet.load_journal(result["supervisor_dir"])
        es = _fleet.elastic_summary(sup_run)
        if not es or es.get("restarts") != 2 or \
                es.get("watchdog_kills") != 1:
            failures.append(f"supervisor journal lost the elastic "
                            f"story: {es}")
    except Exception as e:
        failures.append(f"per-rank journal check failed: "
                        f"{type(e).__name__}: {e}")
    result["failures"] = failures
    if verbose:
        for a in sup.state["attempts"]:
            print(f"  attempt: {a}")
        print(f"  counters: {counters}  wall: {wall_s:.1f}s")
    if not keep_root and not failures:
        import shutil

        shutil.rmtree(root, ignore_errors=True)
        result["root"] = None
    return result


_DRILL_CACHE = None


def drill_result():
    """Run :func:`run_drill` once per PROCESS and cache the result —
    chaos_run's worker_kill/worker_hang/preempt_signal scenarios, this
    tool's own self-test, and fleet_report's per-rank/merged-trace
    checks each assert their own facet of the SAME drill. The cache
    lives on the (shared) ``paddle_tpu.resilience.elastic`` module,
    not here: test_tooling imports every tool as its own module
    instance, and a per-instance global would re-run the whole
    multi-process drill once per consumer. The kept scratch root is
    removed at interpreter exit."""
    global _DRILL_CACHE
    if _DRILL_CACHE is None:
        import paddle_tpu.resilience.elastic as _elastic

        shared = getattr(_elastic, "_ELASTIC_RUN_DRILL_CACHE", None)
        if shared is None:
            shared = run_drill(keep_root=True)
            _elastic._ELASTIC_RUN_DRILL_CACHE = shared
            if shared.get("root"):
                import atexit
                import shutil

                atexit.register(shutil.rmtree, shared["root"],
                                ignore_errors=True)
        _DRILL_CACHE = shared
    return _DRILL_CACHE


def run_budget_drill():
    """Restart-budget exhaustion must surface a CLEAN error carrying the
    attempt history — not a hang, not a stack of orphans."""
    from paddle_tpu.resilience import ElasticBudgetError, GangSupervisor

    sup = GangSupervisor(
        [sys.executable, "-c", "import sys; sys.exit(1)"],
        nprocs=1, max_restarts=1, poll_interval_s=0.01,
        backoff_s=0.0, jitter=0.0, term_grace_s=0.5)
    try:
        sup.run()
    except ElasticBudgetError as e:
        assert len(e.history) == 2, e.history
        assert all(a["kind"] == "crash" and a["code"] == 1
                   for a in e.history), e.history
        assert sup.state["exit_code"] == 1, sup.state
        return f"budget exhausted cleanly after {len(e.history)} attempts"
    raise AssertionError("budget exhaustion did not raise "
                         "ElasticBudgetError")


def self_test():
    failures = []
    try:
        msg = run_budget_drill()
        print(f"  budget_drill   ok — {msg}")
    except Exception as e:
        print(f"  budget_drill   FAILED — {type(e).__name__}: {e}")
        failures.append("budget_drill")

    res = drill_result()  # shared with chaos_run / fleet_report
    if res["failures"]:
        for f in res["failures"]:
            print(f"  drill          FAILED — {f}")
        failures.append("drill")
    else:
        print(f"  drill          ok — kill+hang+preempt survived, "
              f"{len(res['reference'])} steps bitwise vs reference, "
              f"per-rank journals parseable, {res['wall_s']:.1f}s")

    # the supervisor's flight record must tell the elasticity story:
    # run_report's elastic summary is how goodput loss gets attributed
    # (the supervisor journals into <run>/supervisor since the per-rank
    # journal split — workers own the rank_NN subdirs)
    rr = _load_sibling("run_report")
    es = rr.elastic_summary(rr.load_run(res["supervisor_dir"]))
    for key, want in (("restarts", 2), ("preemptions", 1),
                      ("watchdog_kills", 1)):
        if not es or es.get(key) != want:
            print(f"  journal        FAILED — elastic summary {key} "
                  f"{es and es.get(key)} != {want} ({es})")
            failures.append("journal")
            break
    else:
        if not es.get("resume_ms_p50"):
            print(f"  journal        FAILED — no resume latency "
                  f"samples in {es}")
            failures.append("journal")
        else:
            print(f"  journal        ok — {es}")
    # the drill root is SHARED (fleet_report's self-test reads the
    # same rank journals/traces later in one pytest process): cleanup
    # belongs to drill_result's atexit hook, not here
    if failures:
        print(f"self-test FAILED: {failures}")
        return 1
    print("self-test passed: the gang survives kill/hang/preemption with "
          "a bitwise-identical trajectory, and budget exhaustion is a "
          "clean error")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worker", action="store_true",
                    help="run as a gang worker (internal)")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--sync-dir", default=None)
    ap.add_argument("--out-dir", default=None)
    ap.add_argument("--barrier-timeout", type=float, default=60.0)
    ap.add_argument("--kill-at", type=int, default=3)
    ap.add_argument("--hang-at", type=int, default=6)
    ap.add_argument("--preempt-at", type=int, default=9)
    ap.add_argument("--budget-drill", action="store_true",
                    help="only the restart-budget exhaustion drill")
    ap.add_argument("--keep", action="store_true",
                    help="keep the drill's scratch directory")
    ap.add_argument("--self-test", action="store_true")
    args = ap.parse_args(argv)
    if args.worker:
        for req in ("ckpt_dir", "sync_dir", "out_dir"):
            if getattr(args, req) is None:
                ap.error(f"--worker requires --{req.replace('_', '-')}")
        return worker_main(args)
    if args.self_test:
        return self_test()
    if args.budget_drill:
        print(run_budget_drill())
        return 0
    res = run_drill(steps=args.steps, kill_at=args.kill_at,
                    hang_at=args.hang_at, preempt_at=args.preempt_at,
                    keep_root=args.keep, verbose=True)
    for f in res["failures"]:
        print(f"FAILED: {f}")
    if not res["failures"]:
        print(f"drill passed: {res['attempt_kinds']} -> bitwise-identical "
              f"trajectory over {len(res['reference'])} steps")
    return 1 if res["failures"] else 0


if __name__ == "__main__":
    sys.exit(main())
