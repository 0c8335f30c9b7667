"""Shared glue for the example workloads.

Every reference example follows one shape (``examples/md17/md17.py:36-105``):
load the JSON config next to the script, build/load a dataset, split it,
make loaders, derive config fields from the data, build the model, train,
save. This module is that shape for the TPU framework so each example stays
focused on its dataset.

All examples run OFFLINE: this environment has no network egress, so each
example ships a deterministic synthetic generator producing data in the same
schema as the real workload (drop real data in the same directory layout to
use it instead). Generators are seeded — reruns are reproducible.
"""

import json
import os
import sys

import numpy as np

# examples run from a checkout without installation: repo root on the path
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import hydragnn_tpu
from hydragnn_tpu.data import create_dataloaders, split_dataset
from hydragnn_tpu.models import create_model_config
from hydragnn_tpu.parallel.distributed import setup_distributed
from hydragnn_tpu.parallel.mesh import default_mesh
from hydragnn_tpu.train import Trainer, save_model, train_validate_test
from hydragnn_tpu.utils import print_utils
from hydragnn_tpu.utils.config import save_config, update_config


def load_config(example_file: str, name: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(example_file)), name)) as f:
        return apply_cli_overrides(json.load(f))


def apply_cli_overrides(config: dict) -> dict:
    """Map hyperparameter CLI flags into the config — the flag set the
    reference's HPO trial launcher passes to its training scripts
    (``gfm_deephyper_multi.py:70-80``), so ``TrialLauncher`` works against
    any example unchanged."""
    arch = config["NeuralNetwork"]["Architecture"]
    training = config["NeuralNetwork"]["Training"]
    v = example_arg("model_type")
    if v:
        arch["model_type"] = v
    for key in ("hidden_dim", "num_conv_layers"):
        v = example_arg(key)
        if v is not None:
            arch[key] = int(v)
    num_headlayers = example_arg("num_headlayers")
    dim_headlayers = example_arg("dim_headlayers")
    if num_headlayers is not None or dim_headlayers is not None:
        for head in arch["output_heads"].values():
            if num_headlayers is not None:
                head["num_headlayers"] = int(num_headlayers)
            n = int(num_headlayers or head["num_headlayers"])
            if dim_headlayers is not None:
                head["dim_headlayers"] = [int(dim_headlayers)] * n
            elif len(head["dim_headlayers"]) != n:
                head["dim_headlayers"] = [head["dim_headlayers"][0]] * n
    v = example_arg("learning_rate")
    if v is not None:
        training["Optimizer"]["learning_rate"] = float(v)
    for key in ("num_epoch", "batch_size"):
        v = example_arg(key)
        if v is not None:
            training[key] = int(v)
    v = example_arg("steps_per_dispatch")
    if v is True:
        raise SystemExit(
            "--steps_per_dispatch needs a value (steps per XLA dispatch; "
            "0/off disables stacking), e.g. --steps_per_dispatch 8"
        )
    if v is not None:
        # falsy spellings disable stacking (trainer treats 1 as the plain
        # per-batch path), matching the other boolean-ish flags
        if str(v).lower() in ("0", "off", "false", "no"):
            training["steps_per_dispatch"] = 1
        else:
            try:
                training["steps_per_dispatch"] = int(v)
            except ValueError:
                raise SystemExit(
                    f"--steps_per_dispatch: expected an integer or "
                    f"0/off, got {v!r}"
                )
    # execution-mode flags (every example gets them for free):
    # --device-resident stages the training set in HBM; --fit-chunk N
    # additionally runs whole-training chunks as single XLA dispatches
    if example_arg("device-resident"):
        training["device_resident_dataset"] = True
    v = example_arg("fit-chunk")
    if v is True:
        raise SystemExit(
            "--fit-chunk needs a value (epochs per whole-training "
            "dispatch), e.g. --fit-chunk 10"
        )
    if v is not None:
        training["device_resident_dataset"] = True
        training["fit_chunk_epochs"] = int(v)
    return config


def example_flag(flag: str) -> bool:
    """Boolean flag reader: bare ``--foo`` or truthy value is True;
    ``--foo=0`` / ``--foo=false`` is explicitly False."""
    v = example_arg(flag)
    if v is None:
        return False
    if v is True:
        return True
    return str(v).lower() not in ("0", "false", "no", "off")


def example_arg(flag: str, default=None):
    """Tiny argv reader: ``--key=value``, ``--key value``, or bare ``--key``
    (boolean). Examples use a handful of flags; both spellings work."""
    prefix = f"--{flag}="
    argv = sys.argv[1:]
    for i, a in enumerate(argv):
        if a.startswith(prefix):
            return a[len(prefix):]
        if a == f"--{flag}":
            nxt = argv[i + 1] if i + 1 < len(argv) else None
            if nxt is not None and not nxt.startswith("--"):
                return nxt
            return True
    return default


def train_example(config: dict, dataset, log_name: str, seed: int = 0):
    """Split -> loaders -> train. See :func:`train_with_loaders`."""
    training = config["NeuralNetwork"]["Training"]
    trainset, valset, testset = split_dataset(
        dataset, training["perc_train"], False
    )
    return train_with_loaders(
        config, trainset, valset, testset, log_name, seed=seed
    )


def train_with_loaders(config, trainset, valset, testset, log_name, seed=0):
    """Loaders -> derived config -> model -> train -> save.

    Accepts pre-split datasets (lists or shard/dist datasets). Returns
    (state, trainer, val_loss). Prints ``Val Loss: <x>`` at the end — the
    HPO launcher greps exactly that (the reference's DeepHyper trial
    parser, ``gfm_deephyper_multi.py:34-40``).
    """
    setup_distributed()
    verbosity = config.get("Verbosity", {}).get("level", 0)
    suffix = example_arg("log_name_suffix")
    if suffix:
        log_name = f"{log_name}_{suffix}"
    print_utils.setup_log(log_name)

    training = config["NeuralNetwork"]["Training"]
    from hydragnn_tpu.ops.agg_policy import (
        arch_for_auto_policy,
        needs_dense_neighbors,
    )
    from hydragnn_tpu.models.create import needs_edge_offsets

    arch_cfg = config["NeuralNetwork"]["Architecture"]
    need_triplets = arch_cfg.get("model_type") == "DimeNet"
    train_loader, val_loader, test_loader = create_dataloaders(
        trainset, valset, testset, training["batch_size"], need_triplets,
        need_neighbors=needs_dense_neighbors(
            arch_for_auto_policy(config["NeuralNetwork"])
        ),
        num_buckets=training.get("batch_buckets"),
        contiguous_buckets=training.get("contiguous_buckets"),
        bucket_graph_cap=training.get("bucket_graph_cap", "batch"),
        need_offsets=needs_edge_offsets(arch_cfg),
    )
    config = update_config(config, train_loader, val_loader, test_loader)
    save_config(config, log_name)

    arch = dict(config["NeuralNetwork"]["Architecture"])
    arch["loss_function_type"] = training.get("loss_function_type", "mse")
    arch["conv_checkpointing"] = training.get("conv_checkpointing", False)
    model = create_model_config(arch, verbosity)
    trainer = Trainer(model, training, mesh=default_mesh(), verbosity=verbosity)
    state = trainer.init_state(next(iter(train_loader)), seed=seed)

    state = train_validate_test(
        trainer,
        state,
        train_loader,
        val_loader,
        test_loader,
        config["NeuralNetwork"],
        log_name,
        verbosity,
    )
    save_model(state, log_name)
    val_loss, _ = trainer.evaluate(state, val_loader)
    print(f"Val Loss: {val_loss}")
    return state, trainer, float(val_loss)


def train_with_stream(config, sources, valset, testset, log_name,
                      weights=None, seed=0):
    """:func:`train_with_loaders`'s streaming twin: the TRAIN split never
    materializes — ``sources`` are :class:`~hydragnn_tpu.data.stream.
    StreamSource`\\ s fed through the weighted mix, the auto-tuned bucket
    planner replaces the hand ``batch_buckets`` table, and config
    derivation runs over a cursor-neutral probe window (docs/data.md)."""
    from hydragnn_tpu.data.stream import assemble_stream_loaders
    from hydragnn_tpu.models.create import needs_edge_offsets
    from hydragnn_tpu.obs import runtime as obs

    setup_distributed()
    verbosity = config.get("Verbosity", {}).get("level", 0)
    suffix = example_arg("log_name_suffix")
    if suffix:
        log_name = f"{log_name}_{suffix}"
    print_utils.setup_log(log_name)

    training = config["NeuralNetwork"]["Training"]
    scfg = config.get("Dataset", {}).get("streaming", {})
    train_loader, val_loader, test_loader, probe_loader = (
        assemble_stream_loaders(
            sources, weights, training["batch_size"], scfg, valset,
            testset, num_buckets=training.get("batch_buckets"),
            need_offsets=needs_edge_offsets(
                config["NeuralNetwork"]["Architecture"]
            ),
        )
    )
    if train_loader.plan_event:
        obs.emit("bucket_plan", **train_loader.plan_event)
    config = update_config(config, probe_loader, val_loader, test_loader)
    save_config(config, log_name)

    arch = dict(config["NeuralNetwork"]["Architecture"])
    arch["loss_function_type"] = training.get("loss_function_type", "mse")
    arch["conv_checkpointing"] = training.get("conv_checkpointing", False)
    model = create_model_config(arch, verbosity)
    trainer = Trainer(model, training, mesh=default_mesh(),
                      verbosity=verbosity)
    state = trainer.init_state(train_loader.example_batch(), seed=seed)

    state = train_validate_test(
        trainer,
        state,
        train_loader,
        val_loader,
        test_loader,
        config["NeuralNetwork"],
        log_name,
        verbosity,
    )
    save_model(state, log_name)
    val_loss, _ = trainer.evaluate(state, val_loader)
    print(f"Val Loss: {val_loss}")
    return state, trainer, float(val_loss)


# ---------------------------------------------------------------------------
# Synthetic molecule/crystal builders shared by several examples.
# ---------------------------------------------------------------------------

def random_molecule(rng, elements, n_atoms, spread=1.5):
    """Random cloud molecule: atomic numbers z and jittered positions with a
    minimum-distance relaxation so radius graphs are well conditioned."""
    z = rng.choice(elements, size=n_atoms)
    pos = rng.normal(0.0, spread, (n_atoms, 3))
    for _ in range(10):  # push overlapping atoms apart
        d = pos[:, None, :] - pos[None, :, :]
        dist = np.linalg.norm(d, axis=-1) + np.eye(n_atoms)
        push = (dist < 0.8) & ~np.eye(n_atoms, dtype=bool)
        if not push.any():
            break
        pos += 0.25 * (d / dist[..., None] * push[..., None]).sum(axis=1)
    return z.astype(np.float32), pos.astype(np.float32)


def molecule_graph(z, pos, radius, max_neighbours=None, targets=(),
                   target_types=()):
    """GraphData with radius-graph edges and per-head targets."""
    from hydragnn_tpu.data import GraphData, radius_graph

    d = GraphData(
        x=np.asarray(z, np.float32).reshape(-1, 1),
        pos=np.asarray(pos, np.float32),
    )
    d.edge_index = radius_graph(
        d.pos, radius, max_neighbours if max_neighbours else 32
    )
    d.targets = [np.asarray(t, np.float32) for t in targets]
    d.target_types = list(target_types)
    return d


_SMILES_CORES = ["C", "CC", "CCC", "CCCC", "c1ccccc1", "C1CCCCC1",
                 "c1ccncc1", "C1CCOC1"]
_SMILES_SUBS = ["", "O", "N", "F", "C#N", "C(=O)O", "CO", "C=C", "S"]


def random_smiles(rng, max_subs=2):
    """Small random organic molecule as a SMILES string (offline stand-in
    for a real SMILES CSV; parseable by the built-in parser)."""
    core = _SMILES_CORES[int(rng.integers(len(_SMILES_CORES)))]
    subs = [
        _SMILES_SUBS[int(rng.integers(len(_SMILES_SUBS)))]
        for _ in range(int(rng.integers(0, max_subs + 1)))
    ]
    out = core
    for s in subs:
        if s:
            out += f"({s})" if out[-1].isalnum() else s
    return out


def pair_potential_forces(z, pos, cutoff=3.0, r0=1.5, w_scale=0.05):
    """Smooth species-weighted pair potential of the OBSERVED configuration
    and its exact analytic forces.

    phi(r) = w_ij (r - r0)^2 s(r) with the cosine cutoff
    s(r) = 0.5 (1 + cos(pi r / rc)); w_ij = w_scale * sqrt(z_i z_j).
    Returns (total energy, per-atom forces = -grad E). Both are closed-form
    functions of (z, pos) alone — no latent state — so a GNN can learn them
    from single frames (the property the reference's deterministic targets
    have, ``/root/reference/tests/deterministic_graph_data.py:160-193``).
    """
    pos = np.asarray(pos, np.float64)
    dvec = pos[:, None, :] - pos[None, :, :]
    r = np.linalg.norm(dvec, axis=-1)
    np.fill_diagonal(r, np.inf)
    phi, dphi, inside = _pair_terms(z, r, cutoff, r0, w_scale)
    energy = float(phi.sum() / 2.0)  # each pair counted twice
    with np.errstate(invalid="ignore"):
        unit = np.where(inside[..., None], dvec / r[..., None], 0.0)
    forces = -(dphi[..., None] * unit).sum(axis=1)
    return energy, forces


def _pair_terms(z, r, cutoff, r0, w_scale):
    """Shared pair-potential core: phi(r), dphi/dr, and the inside-cutoff
    mask from a pairwise distance matrix (diagonal pre-set to inf). The
    single place the functional form lives — both the free-space and the
    minimum-image labels call through here."""
    zz = np.asarray(z, np.float64)
    w = w_scale * np.sqrt(zz[:, None] * zz[None, :])
    inside = r < cutoff
    rc = float(cutoff)
    rs = np.where(inside, r, rc)  # finite stand-in outside the cutoff
    s = np.where(inside, 0.5 * (1.0 + np.cos(np.pi * rs / rc)), 0.0)
    ds = np.where(inside, -0.5 * np.pi / rc * np.sin(np.pi * rs / rc), 0.0)
    dr = rs - r0
    phi = w * dr**2 * s
    dphi = w * (2.0 * dr * s + dr**2 * ds)  # dphi/dr
    return phi, dphi, inside


def pbc_pair_energy(z, pos, cell, cutoff=3.0, r0=2.0, w_scale=0.05):
    """Minimum-image (diagonal-cell) variant of the pair potential in
    :func:`pair_potential_forces` — energy only.

    Same smooth functional form (shared :func:`_pair_terms` core),
    distances taken through the periodic cell so slab workloads get a
    label that is a continuous function of the observed geometry. Valid
    while ``cutoff < min(diag(cell)) / 2`` (the minimum-image criterion),
    which the OC20 slab satisfies (cutoff 3.5, in-plane period 7.2)."""
    pos = np.asarray(pos, np.float64)
    period = np.diag(np.asarray(cell, np.float64))
    dvec = pos[:, None, :] - pos[None, :, :]
    dvec -= np.round(dvec / period) * period
    r = np.linalg.norm(dvec, axis=-1)
    np.fill_diagonal(r, np.inf)
    phi, _, _ = _pair_terms(z, r, cutoff, r0, w_scale)
    return float(phi.sum() / 2.0)


def pairwise_energy(z, pos, cutoff=3.0):
    """Deterministic smooth 'potential': element-weighted pair interaction
    within a cutoff. Learnable from (z, pos); plays the role of a real label."""
    zz = np.asarray(z, np.float64)
    d = np.linalg.norm(pos[:, None, :] - pos[None, :, :], axis=-1)
    n = len(zz)
    mask = (d < cutoff) & ~np.eye(n, dtype=bool)
    with np.errstate(divide="ignore", invalid="ignore"):
        contrib = np.where(mask, np.sqrt(zz[:, None] * zz[None, :]) / (d + 1.0), 0.0)
    return float(contrib.sum() / (2 * n))
