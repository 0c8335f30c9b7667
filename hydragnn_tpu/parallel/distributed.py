"""Distributed runtime bootstrap & host-side collectives.

Replaces the reference's dual NCCL/Gloo + mpi4py stack
(``hydragnn/utils/distributed.py:120-191``, SURVEY.md §5) with ONE path:
``jax.distributed.initialize`` for multi-host bootstrap (env-driven, with
SLURM/OpenMPI auto-detection like the reference's scheduler sniffing at
``distributed.py:87-104``), XLA collectives inside jitted programs for all
gradient/metric reductions, and ``multihost_utils`` for the few host-side
data-plane reductions (dataset statistics).
"""

import os
from typing import Tuple

import numpy as np


_initialized = False


def setup_distributed() -> Tuple[int, int]:
    """Bootstrap multi-host JAX if a cluster environment is detected.

    Returns (world_size, rank) in terms of *processes* (hosts). On a single
    host this is (1, 0) and no initialization is needed — the device mesh
    still spans all local devices.

    Scheduler detection parallels ``setup_ddp`` (``distributed.py:120-191``):
    SLURM (SLURM_PROCID/SLURM_NTASKS), OpenMPI (OMPI_COMM_WORLD_*), or
    explicit HYDRAGNN_TPU_COORDINATOR / num_processes / process_id env vars.
    JAX's own TPU-pod auto-detection handles TPU VMs natively.
    """
    global _initialized
    import jax

    if _initialized:
        return jax.process_count(), jax.process_index()

    coordinator = os.getenv("HYDRAGNN_TPU_COORDINATOR")
    num_procs = os.getenv("HYDRAGNN_TPU_NUM_PROCESSES")
    proc_id = os.getenv("HYDRAGNN_TPU_PROCESS_ID")
    if coordinator is None and os.getenv("SLURM_NTASKS"):
        num_procs = os.getenv("SLURM_NTASKS")
        proc_id = os.getenv("SLURM_PROCID")
        nodelist = os.getenv("SLURM_NODELIST", "")
        head = parse_slurm_nodelist(nodelist)[0] if nodelist else None
        port = os.getenv("HYDRAGNN_TPU_PORT", "12355")
        coordinator = f"{head}:{port}" if head else None
    elif coordinator is None and os.getenv("OMPI_COMM_WORLD_SIZE"):
        num_procs = os.getenv("OMPI_COMM_WORLD_SIZE")
        proc_id = os.getenv("OMPI_COMM_WORLD_RANK")

    if num_procs is not None and int(num_procs) > 1:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=int(num_procs),
            process_id=int(proc_id) if proc_id is not None else None,
        )
        _initialized = True
    return jax.process_count(), jax.process_index()


def host_tpu_chips() -> int:
    """TPU chips of THIS host, counted from their device files (one
    ``/dev/vfio/<n>`` each on a v5e host): a launcher asks before it
    spawns, and must not start JAX to find out — a parent that has touched
    JAX holds the chip its children need."""
    import glob

    return len(glob.glob("/dev/vfio/[0-9]*"))


def require_one_process_per_chip(n_children: int, child_env, what: str):
    """A chip belongs to one process at a time. A launcher that hands every
    child the same environment gives each the same view of the host's
    chips, so of several concurrent children on one TPU host all but the
    first would fail or hang at start-up — say so and fail here instead.
    Children pinned to the CPU never ask for a chip."""
    chips = host_tpu_chips()
    if n_children < 2 or not chips:
        return
    if (child_env.get("JAX_PLATFORMS") or "").split(",")[0] == "cpu":
        return
    raise RuntimeError(
        f"{what}: {n_children} concurrent child processes would each ask "
        f"for this host's {chips} TPU chip(s); a chip belongs to "
        "one process at a time, so all but the first would fail or hang at "
        "start-up. Run one chip-using child per host, or pin the children "
        "to the CPU (JAX_PLATFORMS=cpu)."
    )


def get_comm_size_and_rank() -> Tuple[int, int]:
    import jax

    try:
        return jax.process_count(), jax.process_index()
    except Exception:
        return 1, 0


def nsplit(seq, n):
    """Split ``seq`` into ``n`` nearly-even chunks (``distributed.py:287-289``)."""
    k, m = divmod(len(seq), n)
    return (
        seq[i * k + min(i, m) : (i + 1) * k + min(i + 1, m)] for i in range(n)
    )


def check_remaining(elapsed_per_epoch: float) -> bool:
    """SLURM wall-clock guard (``distributed.py:317-342``): True if there is
    enough queue time left for one more epoch. Non-SLURM -> always True."""
    job = os.getenv("SLURM_JOB_ID")
    if job is None:
        return True
    import subprocess

    try:
        out = subprocess.run(
            ["squeue", "-h", "-j", job, "-o", "%L"],
            capture_output=True,
            text=True,
            timeout=30,
        ).stdout.strip()
    except Exception:
        return True
    seconds = _parse_slurm_timeleft(out)
    return seconds is None or seconds > 1.2 * elapsed_per_epoch


def _parse_slurm_timeleft(s: str):
    # formats: D-HH:MM:SS, HH:MM:SS, MM:SS, SS, INVALID
    if not s or "INVALID" in s.upper():
        return None
    days = 0
    if "-" in s:
        d, s = s.split("-", 1)
        days = int(d)
    parts = [int(p) for p in s.split(":")]
    while len(parts) < 3:
        parts.insert(0, 0)
    h, m, sec = parts[-3:]
    return ((days * 24 + h) * 60 + m) * 60 + sec


def parse_slurm_nodelist(nodelist: str):
    """Expand 'frontier[00001-00005,00007]' style lists
    (``distributed.py:53-84``)."""
    if "[" not in nodelist:
        return nodelist.split(",")
    prefix, rest = nodelist.split("[", 1)
    body = rest.rstrip("]").split("]")[0]
    nodes = []
    for piece in body.split(","):
        if "-" in piece:
            lo, hi = piece.split("-")
            width = len(lo)
            for v in range(int(lo), int(hi) + 1):
                nodes.append(f"{prefix}{v:0{width}d}")
        else:
            nodes.append(prefix + piece)
    return nodes


def host_allreduce(arr: np.ndarray, op: str = "sum") -> np.ndarray:
    """Host-side all-reduce across processes for data-plane statistics
    (degree histograms, feature min/max) — the role mpi4py plays in the
    reference's data layer (SURVEY.md §2.3). Single-process: identity."""
    import jax

    if jax.process_count() == 1:
        return arr
    from jax.experimental import multihost_utils
    import jax.numpy as jnp

    arr = np.asarray(arr)
    if op == "sum":
        return np.asarray(
            multihost_utils.process_allgather(jnp.asarray(arr)).sum(axis=0)
        )
    if op == "max":
        return np.asarray(
            multihost_utils.process_allgather(jnp.asarray(arr)).max(axis=0)
        )
    if op == "min":
        return np.asarray(
            multihost_utils.process_allgather(jnp.asarray(arr)).min(axis=0)
        )
    raise ValueError(f"unknown op {op}")


def host_allgather_int(value: int):
    """Per-process int -> list over all processes (ordered by process id)."""
    import jax

    if jax.process_count() == 1:
        return [int(value)]
    from jax.experimental import multihost_utils
    import jax.numpy as jnp

    out = multihost_utils.process_allgather(jnp.asarray([value]))
    return [int(v) for v in np.asarray(out).ravel()]


def print_peak_memory(verbosity: int = 0, prefix: str = ""):
    """Device-memory report (analog of ``print_peak_memory``,
    ``distributed.py:277-284``).

    One device lacking ``memory_stats()`` must not hide the rest
    (``continue``, not ``return`` — the old early-return skipped every
    remaining device). Output goes through the obs layer: a
    ``device_memory`` event when telemetry is live, plus the rank-0
    console line (always — a diagnostic named print_* must not be a
    silent no-op at the default verbosity; non-zero ranks report via the
    event stream only)."""
    import jax

    from hydragnn_tpu.obs import runtime as obs
    from hydragnn_tpu.utils.print_utils import print_master

    devices = []
    for d in jax.local_devices():
        try:
            stats = d.memory_stats()
        except Exception:
            continue
        if not stats:
            continue
        peak = int(stats.get("peak_bytes_in_use", 0))
        devices.append(
            {
                "device": str(d),
                "peak_bytes_in_use": peak,
                "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            }
        )
        print_master(
            f"{prefix} {d}: peak {peak / 2**20:.1f} MB",
            verbosity_level=verbosity,
        )
    if devices:
        obs.emit("device_memory", prefix=prefix, devices=devices)
