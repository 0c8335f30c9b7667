"""Worker + orchestration for the elastic kill-and-rejoin e2e (NOT a
pytest module — ``tests/test_elastic.py`` and the CI smoke drive it).

Three entry points:

    python _elastic_worker.py worker <workdir>
        The training payload one :class:`ElasticAgent` supervises: a small
        deterministic run through the REAL epoch driver with per-epoch
        resumable checkpoints and async checkpointing, heartbeat lease +
        peer watchdog from ``HYDRAGNN_ELASTIC_*`` env (set by the agent).
        Resumes from the rolling checkpoint whenever one exists — which is
        exactly what a respawn at a new world size does. Rank 0 activates
        run telemetry, so ``<workdir>/logs/elastic/events.jsonl`` carries
        the ``host_lost``/``world_resize`` record across generations, and
        writes ``result.json`` at clean completion.

    python _elastic_worker.py agent <workdir> <host> <n_hosts> <base_port>
        One per-host supervisor (``hydragnn_tpu.train.elastic.ElasticAgent``)
        wrapping the worker above.

    run_elastic(workdir, n_hosts, ...)
        Test-side helper: spawn the N agents, wait for all, return exit
        codes. Fault injection (e.g. ``HYDRAGNN_FAULT_LOSE_HOST_AT_STEP``)
        rides in via ``extra_env``.
"""

import json
import os
import subprocess
import sys

NUM_EPOCH = 8
LOG_NAME = "elastic"
# aggressive lease tuning: detection must outrun the (deliberately
# slowed) survivor finishing the whole run before the re-mesh happens
HEARTBEAT_S = "0.1"
LEASE_S = "0.75"


def _repo_root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- training payload ------------------------------------------------------


def make_samples(num=24, seed=11):
    import numpy as np

    from hydragnn_tpu.data.dataobj import GraphData

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(num):
        n = 6
        g = GraphData()
        g.x = rng.random((n, 1)).astype(np.float32)
        g.pos = rng.random((n, 3)).astype(np.float32)
        src = np.arange(n)
        dst = (src + 1) % n
        g.edge_index = np.stack(
            [np.concatenate([src, dst]), np.concatenate([dst, src])]
        ).astype(np.int64)
        g.edge_attr = None
        g.targets = [np.array([g.x.sum()], np.float32), g.x.copy()]
        g.target_types = ["graph", "node"]
        out.append(g)
    return out


def _start_barrier(barrier_dir, rank, world, timeout_s=120.0):
    """Best-effort rendezvous: every rank drops a file, then waits until
    ``world`` files exist. A respawn inside one generation finds its peers'
    files already there and proceeds at once."""
    import time

    os.makedirs(barrier_dir, exist_ok=True)
    open(os.path.join(barrier_dir, str(rank)), "w").close()
    deadline = time.monotonic() + timeout_s
    while (
        len(os.listdir(barrier_dir)) < world and time.monotonic() < deadline
    ):
        time.sleep(0.02)


def worker_main(workdir):
    # ONE virtual CPU device per process; must happen before backend init
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=1"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")

    sys.path.insert(0, _repo_root())
    os.chdir(workdir)

    import numpy as np

    from hydragnn_tpu.obs import runtime as obs
    from hydragnn_tpu.parallel.distributed import setup_distributed
    from hydragnn_tpu.train import elastic
    from hydragnn_tpu.train.checkpoint import (
        checkpoint_exists,
        drain_async,
        load_state_dict,
        pop_train_meta,
        restore_into,
        rolling_checkpoints,
    )
    from hydragnn_tpu.train.epoch_driver import train_validate_test

    world, rank = setup_distributed()
    # the lease must exist before the (slow) build/compile below — a
    # compiling peer is not a dead peer
    rt = elastic.maybe_elastic()

    from hydragnn_tpu.data.loaders import GraphLoader, compute_layout
    from hydragnn_tpu.models.create import create_model_config
    from hydragnn_tpu.train.trainer import Trainer

    arch = {
        "model_type": "GIN",
        "input_dim": 1,
        "hidden_dim": 8,
        "num_conv_layers": 2,
        "output_dim": [1, 1],
        "output_type": ["graph", "node"],
        "output_heads": {
            "graph": {
                "num_sharedlayers": 1,
                "dim_sharedlayers": 8,
                "num_headlayers": 1,
                "dim_headlayers": [8],
            },
            "node": {
                "num_headlayers": 1,
                "dim_headlayers": [8],
                "type": "mlp",
            },
        },
        "task_weights": [1.0, 1.0],
    }
    training = {
        "num_epoch": NUM_EPOCH,
        "Optimizer": {"type": "AdamW", "learning_rate": 1e-2},
        "resume_every": 1,
        # retain every epoch: the e2e compares against the exact rolling
        # checkpoint the resized world resumed from
        "checkpoint_keep_last": NUM_EPOCH + 2,
        "async_checkpoint": True,
    }
    samples = make_samples()
    layout = compute_layout([samples], batch_size=4, need_triplets=False)
    # per-process batch shards rebalance by (rank, world) — the loaders'
    # DistributedSampler semantics; a re-meshed world re-derives them
    train_loader = GraphLoader(samples[:16], 4, layout, shuffle=True, seed=7)
    val_loader = GraphLoader(samples[16:20], 4, layout, shuffle=False)
    test_loader = GraphLoader(samples[20:], 4, layout, shuffle=False)
    model = create_model_config(arch)
    # mesh=None: each process trains its local shard on its own device.
    # The CPU PJRT backend has no cross-process XLA collectives
    # ("Multiprocess computations aren't implemented on the CPU backend"
    # — the same limitation tests/test_multiprocess.py documents), and
    # the elasticity machinery under test — jax.distributed bootstrap,
    # heartbeat lease, watchdog, agent re-mesh, checkpoint resume, shard
    # rebalance — is identical either way; on TPU the worker would hand
    # the Trainer the global mesh exactly as the driver does.
    trainer = Trainer(model, training, mesh=None)
    state = trainer.init_state(next(iter(train_loader)), seed=0)

    # all ranks: rank 0 gets the full events.jsonl stream, the other
    # hosts get per-host events-host<k>.jsonl streams (elastic mode) so
    # the fleet rollup sees every host's record
    telemetry = obs.init_run_telemetry(
        {"NeuralNetwork": {"Training": training}}, LOG_NAME
    )

    # start-aligned epoch 0: a file rendezvous on the shared workdir (no
    # XLA collective — works on every backend) removes the multi-second
    # process-startup skew, so a fault at rank K's step N lands while the
    # other ranks are near step N too. On real accelerators the first
    # cross-host collective provides this alignment for free.
    if world > 1:
        _start_barrier(
            # cwd is the workdir (chdir above)
            os.path.join(
                "elastic-coord",
                f"start-gen{os.getenv('HYDRAGNN_ELASTIC_GEN', '0')}",
            ),
            rank, world,
        )

    # resume whenever a checkpoint (or an intact rolling fallback) exists:
    # gen 0 restarts and post-resize respawns share this one path
    resume_meta = None
    if checkpoint_exists(LOG_NAME) or rolling_checkpoints(LOG_NAME):
        restored = load_state_dict(LOG_NAME)
        resume_meta = pop_train_meta(restored)
        state = trainer.place_state(restore_into(state, restored))

    epochs_run = []
    orig = trainer.train_epoch

    def counting_train_epoch(state, loader, rng):
        epochs_run.append(loader.epoch)
        return orig(state, loader, rng)

    trainer.train_epoch = counting_train_epoch

    config_nn = {
        "Training": training,
        "Variables_of_interest": {"output_names": ["sum", "x"]},
    }
    try:
        state = train_validate_test(
            trainer, state, train_loader, val_loader, test_loader,
            config_nn, LOG_NAME, verbosity=0, resume_meta=resume_meta,
        )
        drain_async()
    finally:
        if rt is not None:
            rt.stop()

    if rank == 0:
        from hydragnn_tpu.train.optimizer import get_learning_rate

        result = {
            "world": world,
            "rank": rank,
            "gen": int(os.getenv("HYDRAGNN_ELASTIC_GEN", "0")),
            "resumed_from_epoch": (
                None if resume_meta is None else int(resume_meta["epoch"]) + 1
            ),
            "epochs_run": epochs_run,
            "final_lr": get_learning_rate(state.opt_state),
            "final_params_digest": [
                float(np.asarray(leaf, np.float64).sum())
                for leaf in jax.tree_util.tree_leaves(
                    jax.device_get(state.params)
                )
            ],
        }
        with open("result.json", "w") as f:
            json.dump(result, f)
    if telemetry is not None:
        obs.deactivate(status="complete")


# ---- agent + orchestration -------------------------------------------------


def agent_main(workdir, host, n_hosts, base_port):
    sys.path.insert(0, _repo_root())

    from hydragnn_tpu.train.elastic import ElasticAgent

    agent = ElasticAgent(
        [sys.executable, os.path.abspath(__file__), "worker", workdir],
        coord_dir=os.path.join(workdir, "elastic-coord"),
        host=int(host),
        n_hosts=int(n_hosts),
        base_port=int(base_port),
        heartbeat_s=float(os.getenv("HYDRAGNN_ELASTIC_HEARTBEAT_S",
                                    HEARTBEAT_S)),
        lease_s=float(os.getenv("HYDRAGNN_ELASTIC_LEASE_S", LEASE_S)),
    )
    return agent.run()


def run_elastic(workdir, n_hosts=2, base_port=None, extra_env=None,
                timeout=360):
    """Spawn ``n_hosts`` agents over one shared workdir; wait for all.

    Returns ``{host: returncode}``. The training run's artifacts land in
    ``<workdir>/logs/elastic/`` (checkpoints, events.jsonl, result.json
    at ``<workdir>/result.json``)."""
    import socket

    if base_port is None:
        # a port whose gen-indexed successors are also free enough in
        # practice; bind port 0 once to land in the ephemeral range
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            base_port = s.getsockname()[1]
    env = {
        k: v
        for k, v in os.environ.items()
        if not k.startswith(("HYDRAGNN_FAULT_", "HYDRAGNN_ELASTIC_",
                             "HYDRAGNN_TPU_", "HYDRAGNN_RESUME",
                             "HYDRAGNN_CKPT_", "HYDRAGNN_ASYNC"))
    }
    env.update(
        HYDRAGNN_ELASTIC_HEARTBEAT_S=HEARTBEAT_S,
        HYDRAGNN_ELASTIC_LEASE_S=LEASE_S,
    )
    env.update(extra_env or {})
    procs = {}
    for host in range(n_hosts):
        procs[host] = subprocess.Popen(
            [
                sys.executable, os.path.abspath(__file__), "agent",
                workdir, str(host), str(n_hosts), str(base_port),
            ],
            env=env,
        )
    rcs = {}
    try:
        for host, p in procs.items():
            rcs[host] = p.wait(timeout=timeout)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
    return rcs


if __name__ == "__main__":
    mode = sys.argv[1]
    if mode == "worker":
        worker_main(sys.argv[2])
    elif mode == "agent":
        raise SystemExit(
            agent_main(sys.argv[2], sys.argv[3], sys.argv[4], sys.argv[5])
        )
    else:
        raise SystemExit(f"unknown mode {mode!r}")
