"""Giant-graph workload: ONE large atomistic system partitioned across the
device mesh (graph-partition parallelism).

No reference counterpart — HydraGNN's scaling axis is data parallelism over
many small graphs; a single system larger than one accelerator's memory is
out of its reach. Here a large FCC supercell (default ~16k atoms; set
--num_atoms) is sharded node-wise over all available devices
(``hydragnn_tpu/parallel/graph_partition.py``): Morton-ordered partitions,
halo all_to_all exchanges per conv layer, psum'd BatchNorm/pool/loss, and a
shard_map training step whose gradients are psum'd across shards.

Run on CPU for a quick look:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/giant_graph/train.py --num_atoms 4096 --steps 10
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from common import example_arg  # noqa: E402

sys.path.insert(
    0,
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
)


class _Sample:
    pass


def fcc_supercell(num_atoms: int, seed: int = 0):
    """FCC lattice with thermal displacement; energy/force labels from a
    smooth pair potential (deterministic, offline)."""
    rng = np.random.default_rng(seed)
    cells = max(1, round((num_atoms / 4) ** (1.0 / 3.0)))
    base = np.array(
        [[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]], np.float32
    )
    pos = []
    for i in range(cells):
        for j in range(cells):
            for k in range(cells):
                pos.append(base + np.array([i, j, k], np.float32))
    pos = np.concatenate(pos, 0) * 3.6  # Cu-like lattice constant (A)
    pos = pos + 0.05 * rng.standard_normal(pos.shape).astype(np.float32)
    n = pos.shape[0]

    # radius graph via the framework's cell-list builder
    from hydragnn_tpu.data.radius_graph import radius_graph

    edge_index = radius_graph(pos, radius=3.0, max_neighbors=12)

    s = _Sample()
    s.pos = pos
    s.x = rng.random((n, 1)).astype(np.float32)
    s.edge_index = edge_index
    s.edge_attr = None
    # smooth per-node target + global energy (same flavor as tests/synthetic)
    send, recv = edge_index
    d = np.linalg.norm(pos[send] - pos[recv], axis=1)
    per_edge = np.exp(-d / 2.0)
    node_e = np.zeros(n, np.float32)
    np.add.at(node_e, recv, per_edge.astype(np.float32))
    s.targets = [
        np.array([node_e.mean()], np.float32),
        node_e[:, None] / max(node_e.max(), 1e-6),
    ]
    return s


def main():
    # --cpu_devices N: demo on a virtual CPU mesh (must pin the platform
    # BEFORE the first backend touch — same trick as tests/conftest.py)
    cpu_devices = example_arg("cpu_devices")
    if cpu_devices:
        try:
            cpu_devices = int(cpu_devices)
        except (TypeError, ValueError):
            raise SystemExit("--cpu_devices needs a device count, e.g. --cpu_devices 8")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cpu_devices}"
        ).strip()
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    num_atoms = int(example_arg("num_atoms") or 16384)
    steps = max(int(example_arg("steps") or 20), 5)  # compile + 2 warmup + timed

    import optax

    from hydragnn_tpu.graph import collate_graphs, pad_sizes_for
    from hydragnn_tpu.models import create_model_config, init_model_params
    from hydragnn_tpu.parallel.graph_partition import (
        make_partitioned_train_step,
        partition_graph,
        put_partitioned_batch,
        put_partitioned_state,
    )
    from hydragnn_tpu.parallel.mesh import make_mesh
    from hydragnn_tpu.train.trainer import TrainState

    n_dev = len(jax.devices())
    print(f"devices: {n_dev}, atoms: {num_atoms}")
    sample = fcc_supercell(num_atoms)
    print(f"built graph: {sample.pos.shape[0]} nodes, "
          f"{sample.edge_index.shape[1]} edges")

    arch = {
        "model_type": "PNA",
        "input_dim": 1,
        "hidden_dim": 64,
        "output_dim": [1, 1],
        "output_type": ["graph", "node"],
        "output_heads": {
            "graph": {
                "num_sharedlayers": 1,
                "dim_sharedlayers": 32,
                "num_headlayers": 2,
                "dim_headlayers": [32, 32],
            },
            "node": {
                "num_headlayers": 2,
                "dim_headlayers": [32, 32],
                "type": "mlp",
            },
        },
        "task_weights": [1.0, 1.0],
        "num_conv_layers": 3,
        "pna_deg": list(np.bincount(
            np.bincount(sample.edge_index[1], minlength=sample.pos.shape[0])
        )),
        "equivariance": False,
    }

    # --dense: scatter-free neighbor-list aggregation inside each shard
    # (ops/dense_agg.py; 1.7-3.3x faster at this scale on v5e)
    from common import example_flag

    dense = example_flag("dense")

    t0 = time.time()
    pbatch, info = partition_graph(
        sample, n_dev, ("graph", "node"), (1, 1), order="morton",
        need_neighbors=dense,
    )
    print(f"partitioned in {time.time() - t0:.2f}s: "
          f"{info.nl} nodes/shard, {info.el} edges/shard, halo {info.halo}"
          + (f", dense k_in {info.k_in}" if dense else ""))

    mesh = make_mesh(n_dev, "graph")
    pbatch = put_partitioned_batch(pbatch, mesh, "graph")

    # init params on a single-shard-sized throwaway batch (params depend
    # only on feature dims)
    ref_model = create_model_config(dict(arch))
    small = fcc_supercell(256, seed=1)
    n_pad, e_pad, g_pad = pad_sizes_for(
        small.pos.shape[0], small.edge_index.shape[1], 1
    )
    init_batch = collate_graphs(
        [small], n_pad, e_pad, g_pad, ("graph", "node"), (1, 1), to_device=True
    )
    variables = init_model_params(ref_model, init_batch)

    arch["partition_axis"] = "graph"
    model = create_model_config(arch)
    tx = optax.adamw(1e-3)
    state = TrainState(
        params=variables["params"],
        batch_stats=variables.get("batch_stats", {}),
        opt_state=tx.init(variables["params"]),
        step=np.zeros((), np.int32),
    )
    state = put_partitioned_state(state, mesh)
    step = make_partitioned_train_step(model, tx, mesh, "graph")

    rng = jax.random.PRNGKey(0)
    rng, warm = jax.random.split(rng)
    state, metrics = step(state, pbatch, warm)  # compile
    loss0 = metrics["loss"]
    for _ in range(2):  # settle any backend warmup
        rng, sub = jax.random.split(rng)
        state, metrics = step(state, pbatch, sub)
    jax.block_until_ready(metrics)
    t0 = time.time()
    for i in range(3, steps):
        rng, sub = jax.random.split(rng)
        state, metrics = step(state, pbatch, sub)
    jax.block_until_ready(metrics)
    dt = (time.time() - t0) / max(steps - 3, 1)
    print(f"step 0: loss {float(loss0):.6f}")
    print(
        f"step {steps - 1}: loss {float(metrics['loss']):.6f}  "
        f"({dt * 1e3:.1f} ms/step, {sample.pos.shape[0] / dt:.0f} atoms/sec)"
    )


if __name__ == "__main__":
    main()
