"""Multi-node trial launcher: one training subprocess per trial.

Parity with the reference's DeepHyper multi-node pattern
(``examples/multidataset_hpo/gfm_deephyper_multi.py:22-70``): trial geometry
comes from environment variables, each trial launches an ``srun`` (or plain
``python`` when no scheduler is present) subprocess with hyperparameters as
CLI flags, and the trial metric is the last ``Val Loss: <x>`` printed by the
training script. On TPU pods the launch prefix targets TPU-VM hosts instead
of GPUs-per-node, but the orchestration shape is identical.

Early kill (the HPO half of the elastic-training work, docs/resilience.md):
each trial subprocess writes a heartbeat lease (``HYDRAGNN_HEARTBEAT_FILE``,
served by ``train/elastic.py`` inside the trial) whose payload carries the
step/epoch progress counters and the divergence guard's restore count. The
launcher polls it and KILLS the trial — freeing its node block back to the
pool for the next trial — when the lease goes stale (hung collective, wedged
host) or the guard restores exceed the budget (a diverging config is not
worth its remaining epochs). Every trial outcome lands as a structured
``hpo_trial`` event in ``<log_dir>/trials.jsonl`` (the run-event schema,
``obs/events.py``): completed / failed / killed, with the reason — a
garbled-output trial is marked FAILED there, never silently scored.
"""

import os
import re
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

_VAL_LOSS_RE = re.compile(r"Val Loss: ([-+]?(?:\d+(?:\.\d*)?|\.\d+)(?:[eE][-+]?\d+)?)")


def parse_val_loss(output: str) -> Optional[float]:
    """Last validation loss a training subprocess printed, or None."""
    matches = _VAL_LOSS_RE.findall(output)
    return float(matches[-1]) if matches else None


class TrialLauncher:
    """Builds and runs per-trial training commands.

    Geometry (all optional, env-driven like the reference):
      ``HPO_NNODES_PER_TRIAL``  nodes per trial (srun -N)
      ``HPO_NRANKS_PER_TRIAL``  processes per trial (srun -n)
      ``HPO_LOG_DIR``           where per-trial stdout/stderr land
    ``use_srun`` defaults to auto-detection via ``SLURM_JOB_ID``.

    Early-kill knobs (module docstring; both optional, env-defaulted):
      ``heartbeat_timeout`` / ``HPO_HEARTBEAT_TIMEOUT_S`` — kill a trial
        whose training PROGRESS (the lease's ``progress_ts``, advanced
        per optimizer step) is older than this many seconds (applies
        once the trial has heartbeat at least once — startup/compile
        time before the first beat or step is covered by ``timeout``
        alone). Staged/fit-chunk trials tick progress once per whole
        dispatch: size the timeout above the worst dispatch wall time;
      ``max_guard_restores`` / ``HPO_MAX_GUARD_RESTORES`` — kill a trial
        whose divergence guard restored more than this many times.
    """

    def __init__(
        self,
        script: str,
        log_dir: Optional[str] = None,
        use_srun: Optional[bool] = None,
        base_env: Optional[Dict[str, str]] = None,
        timeout: Optional[float] = None,
        heartbeat_timeout: Optional[float] = None,
        max_guard_restores: Optional[int] = None,
    ):
        self.script = script
        self.log_dir = log_dir or os.environ.get("HPO_LOG_DIR", "./logs/hpo")
        self.nnodes = int(os.environ.get("HPO_NNODES_PER_TRIAL", "1"))
        self.nranks = int(os.environ.get("HPO_NRANKS_PER_TRIAL", "1"))
        self.use_srun = (
            use_srun
            if use_srun is not None
            else "SLURM_JOB_ID" in os.environ
        )
        self.base_env = dict(base_env or {})
        self.timeout = timeout
        if heartbeat_timeout is None:
            env = os.environ.get("HPO_HEARTBEAT_TIMEOUT_S")
            heartbeat_timeout = float(env) if env else None
        self.heartbeat_timeout = heartbeat_timeout
        if max_guard_restores is None:
            env = os.environ.get("HPO_MAX_GUARD_RESTORES")
            max_guard_restores = int(env) if env else None
        self.max_guard_restores = max_guard_restores
        os.makedirs(self.log_dir, exist_ok=True)
        self._events = None
        self._events_lock = threading.Lock()

    def _emit_trial(self, trial_id: int, status: str, **fields):
        """Structured per-trial outcome -> ``<log_dir>/trials.jsonl``
        (schema-valid ``hpo_trial`` events; the study-side record of WHY
        each node-block was freed). Lazy: studies that never launch a
        subprocess never create the file."""
        from hydragnn_tpu.obs.events import RunEventLog

        with self._events_lock:
            if self._events is None:
                self._events = RunEventLog(
                    os.path.join(self.log_dir, "trials.jsonl")
                )
            log = self._events
        log.emit("hpo_trial", trial=int(trial_id), status=status, **fields)

    def build_command(self, trial_id: int, params: Dict[str, object],
                      nodelist: Optional[List[str]] = None) -> List[str]:
        cmd: List[str] = []
        if self.use_srun:
            cmd += ["srun", "-N", str(self.nnodes), "-n", str(self.nranks)]
            if nodelist:
                cmd += [f"--nodelist={','.join(nodelist)}"]
        cmd += [sys.executable, "-u"]
        if sys.flags.no_site:
            # parent launched with -S (site init skipped): children must
            # match or they re-run the site hooks the caller avoided
            cmd.append("-S")
        cmd += [self.script]
        for k, v in params.items():
            cmd.append(f"--{k}={v}")
        cmd.append(f"--log_name_suffix=trial_{trial_id}")
        return cmd

    def _kill_reason(self, hb_path: str, started: float) -> Optional[str]:
        """Early-kill decision for one poll tick (None = keep running)."""
        if self.heartbeat_timeout is None and self.max_guard_restores is None:
            return None
        # the same tolerant reader the lease's writer side uses
        from hydragnn_tpu.train.elastic import _read_json

        hb = _read_json(hb_path)
        if hb is None:
            return None  # no lease yet: startup/compile, timeout covers it
        if (
            self.max_guard_restores is not None
            and int(hb.get("guard_restores", 0)) > self.max_guard_restores
        ):
            return "divergence"
        # staleness reads the TRAINING-PROGRESS timestamp when the trial
        # reports one (elastic note_step/note_epoch): the lease daemon
        # keeps stamping `ts` even while the training thread is wedged in
        # a hung collective — `ts` alone would never detect exactly the
        # hang this kill exists for. Before the first step (compile,
        # data load) only `ts` exists, so a beating-but-not-yet-stepping
        # trial is not killed.
        progress = hb.get("progress_ts") or hb.get("ts", started)
        if (
            self.heartbeat_timeout is not None
            and time.time() - float(progress) > self.heartbeat_timeout
        ):
            return "heartbeat_timeout"
        return None

    def run(self, trial, nodelist: Optional[List[str]] = None) -> float:
        """Launch the trial subprocess; returns val loss (inf on failure).

        The reference returns the string "F" for a failed trial and lets
        DeepHyper discard it; here every non-completed outcome maps to
        +inf (``optimize_concurrent`` tells those as *failed* so the
        sampler never learns from them) AND is recorded as a structured
        ``hpo_trial`` event with the reason. A trial that exits 0 but
        prints no parseable ``Val Loss:`` is a FAILURE (garbled output),
        not a score.
        """
        cmd = self.build_command(trial.number, trial.params, nodelist)
        hb_path = os.path.join(
            self.log_dir, f"heartbeat_{trial.number}.json"
        )
        # a stale lease from a previous study run in the same log_dir
        # (trial numbering restarts at 0) would early-kill the fresh
        # trial before it ever heartbeats — the lease starts clean
        try:
            os.remove(hb_path)
        except OSError:
            pass
        env = {
            **os.environ,
            **self.base_env,
            # the trial-side runtime (train/elastic.py) serves this lease
            "HYDRAGNN_HEARTBEAT_FILE": hb_path,
        }
        out_path = os.path.join(self.log_dir, f"output_{trial.number}.txt")
        started = time.time()
        nodes = list(nodelist or [])
        with open(out_path, "w") as out:
            proc = subprocess.Popen(
                cmd, stdout=out, stderr=subprocess.STDOUT, env=env
            )
            killed_reason = None
            try:
                while True:
                    try:
                        proc.wait(timeout=0.25)
                        break
                    except subprocess.TimeoutExpired:
                        pass
                    elapsed = time.time() - started
                    if self.timeout is not None and elapsed > self.timeout:
                        killed_reason = "timeout"
                    else:
                        killed_reason = self._kill_reason(hb_path, started)
                    if killed_reason is not None:
                        proc.kill()
                        proc.wait(timeout=30)
                        break
            finally:
                if proc.poll() is None:
                    proc.kill()
                    proc.wait(timeout=30)
        if killed_reason is not None:
            self._emit_trial(
                trial.number, "killed", reason=killed_reason,
                wall_s=round(time.time() - started, 3), nodes=nodes,
            )
            return float("inf")
        if proc.returncode != 0:
            self._emit_trial(
                trial.number, "failed",
                reason=f"exit_{proc.returncode}", nodes=nodes,
            )
            return float("inf")
        try:
            with open(out_path) as f:
                text = f.read()
        except OSError:
            text = ""
        val = parse_val_loss(text)
        if val is None:
            # exit 0 with no parseable metric: the reference would feed
            # whatever garbage it matched into the sampler — here it is
            # an explicit failure with its own event, and the caller's
            # +inf contract releases the node block
            self._emit_trial(
                trial.number, "failed", reason="garbled_output",
                nodes=nodes,
            )
            return float("inf")
        self._emit_trial(
            trial.number, "completed", val_loss=float(val),
            wall_s=round(time.time() - started, 3), nodes=nodes,
        )
        return val


class NodePool:
    """Per-trial node-block allocation (the reference pins each DeepHyper
    trial to its own node block via ``--nodelist``,
    ``gfm_deephyper_multi.py:43-70``). ``nodes=None`` (and no
    ``HPO_NODELIST``) disables pinning — trials launch without a
    nodelist."""

    def __init__(self, nodes: Optional[List[str]] = None):
        if nodes is None:
            env = os.environ.get("HPO_NODELIST", "")
            nodes = [n.strip() for n in env.split(",") if n.strip()] or None
        self.free: Optional[List[str]] = list(nodes) if nodes else None

    def slots(self, per_trial: int) -> int:
        if self.free is None:
            return 0
        return len(self.free) // max(per_trial, 1)

    def acquire(self, k: int) -> Optional[List[str]]:
        if self.free is None:
            return None
        if len(self.free) < k:
            raise RuntimeError(
                f"node pool exhausted: need {k}, have {len(self.free)}"
            )
        block, self.free = self.free[:k], self.free[k:]
        return block

    def release(self, block: Optional[List[str]]):
        if block:
            self.free.extend(block)


def optimize_concurrent(
    study,
    launcher: TrialLauncher,
    suggest,
    n_trials: int,
    max_concurrent: Optional[int] = None,
    nodes: Optional[List[str]] = None,
):
    """Concurrent ask/tell search: up to ``max_concurrent`` trial
    subprocesses in flight, each on its own node block — the reference's
    DeepHyper CBO scheduler shape (``gfm_deephyper_multi.py:22-70``: N
    nodes / nodes-per-trial concurrent srun trials, asynchronous
    completion, sampler updated as each trial lands).

    ``suggest(trial)`` draws the hyperparameters (``trial.suggest_*``);
    the launcher turns ``trial.params`` into CLI flags. Failed/timed-out
    trials (+inf) are told as ``failed`` so the sampler never learns from
    them. ``max_concurrent`` defaults to ``HPO_MAX_CONCURRENT``, else the
    node pool's slot count, else 2. Study methods run only on THIS
    thread — worker threads just babysit subprocesses — so the sampler
    needs no locking."""
    from concurrent.futures import (
        FIRST_COMPLETED,
        ThreadPoolExecutor,
        wait,
    )

    pool = NodePool(nodes)
    if max_concurrent is None:
        env = os.environ.get("HPO_MAX_CONCURRENT")
        if env:
            max_concurrent = int(env)
        else:
            max_concurrent = pool.slots(launcher.nnodes) or 2
    if pool.free is not None:
        max_concurrent = min(max_concurrent, pool.slots(launcher.nnodes))
    max_concurrent = max(1, max_concurrent)
    if not launcher.use_srun:
        # local trials share this host (srun gives each its node block)
        from hydragnn_tpu.parallel.distributed import (
            require_one_process_per_chip,
        )

        require_one_process_per_chip(
            max_concurrent, {**os.environ, **launcher.base_env},
            "optimize_concurrent",
        )

    with ThreadPoolExecutor(max_workers=max_concurrent) as ex:
        inflight = {}
        submitted = 0
        try:
            while submitted < n_trials or inflight:
                while (
                    submitted < n_trials
                    and len(inflight) < max_concurrent
                ):
                    trial = study.ask()
                    suggest(trial)
                    block = pool.acquire(launcher.nnodes) if pool.free is not None else None
                    fut = ex.submit(launcher.run, trial, block)
                    inflight[fut] = (trial, block)
                    submitted += 1
                done, _ = wait(inflight, return_when=FIRST_COMPLETED)
                for fut in done:
                    trial, block = inflight.pop(fut)
                    pool.release(block)
                    try:
                        val = fut.result()
                    except Exception:
                        val = float("inf")
                    if val == float("inf"):
                        study.tell(trial, None, state="failed")
                    else:
                        study.tell(trial, val)
        except BaseException:
            # operator interrupt / study crash: queued-but-unstarted
            # trials must not launch AFTER the stop was requested — the
            # pool context below joins only what is already running
            for fut in inflight:
                fut.cancel()
            raise
    return study.best_trial
