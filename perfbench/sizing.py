#!/usr/bin/env python3
"""The sizing rule, run in the sandbox (no chip): compile a cell's largest
bucket's ``train_step`` at a batch rung for a DESCRIBED v5e and print the
compiled peak (arguments + outputs + temporaries).

    JAX_PLATFORMS=cpu python3 perfbench/sizing.py --workload <cell> --rung 768 1024

The rule: the largest rung of 256, 384, 512, 768, 1024, 1536, 2048, ... whose
compiled peak is at most three quarters of the device's memory. A compile
that passes is not a chip run; the chip's own ``peak_bytes_in_use`` is read by
``run.py`` (PERF.md section 4 holds both).
"""

import argparse
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def compiled_peak(workload, rung, batches=2):
    sys.path[:0] = [p for p in (HERE, ROOT) if p not in sys.path]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import build
    import traffic_gen
    from hydragnn_tpu.data.loaders import dataset_loading_and_splitting
    from hydragnn_tpu.models.create import create_model_config
    from hydragnn_tpu.train.driver import _arch_for_factory
    from hydragnn_tpu.train.trainer import Trainer
    from hydragnn_tpu.utils.config import update_config

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell, config, mix = build.load_cell(workload, json.load(f))
    work = tempfile.mkdtemp(prefix="sizing_", dir=os.environ.get("TMPDIR"))
    os.chdir(work)
    # the quantile size law makes a short data set span the same sizes
    graphs = traffic_gen.make_graphs(mix, rung * batches, 0)
    evals = graphs[: mix["eval_graphs"]]
    paths = build.write_dataset(work, graphs, evals)
    one_chip = dict(cell, chips=1)
    cfg = build.hydragnn_config(config, mix, one_chip, paths, rung)
    loaders = dataset_loading_and_splitting(cfg)
    cfg = update_config(cfg, *loaders)
    trainer = Trainer(
        create_model_config(_arch_for_factory(cfg)),
        cfg["NeuralNetwork"]["Training"],
    )
    largest = max(iter(loaders[0]), key=lambda b: b.x.shape[0])
    state = jax.eval_shape(lambda: trainer.init_state(largest))
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=chip), tree
    )
    compiled = trainer._train_step.lower(
        on_chip(state),
        on_chip(trainer._compact_for_transfer(largest)),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip),
    ).compile()
    mem = compiled.memory_analysis()
    return {
        "workload": workload, "rung": rung,
        "largest_bucket": {"nodes": int(largest.x.shape[0]),
                           "edges": int(largest.senders.shape[0])},
        "dense_aggregation": cfg["NeuralNetwork"]["Architecture"].get(
            "dense_aggregation"),
        "compiled_peak_bytes": int(
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
        ),
        "temp_bytes": int(mem.temp_size_in_bytes),
    }


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rung", type=int, nargs="+", required=True)
    args = parser.parse_args()
    for rung in args.rung:
        try:
            print(json.dumps(compiled_peak(args.workload, rung)), flush=True)
        except Exception as e:  # a refusal by the compiler is the answer
            print(json.dumps({"workload": args.workload, "rung": rung,
                              "refused": f"{type(e).__name__}: {e}"[:600]}),
                  flush=True)


if __name__ == "__main__":
    main()
