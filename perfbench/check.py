"""How ``correct`` is decided for a training cell.

Set-up drives the program's own ``Trainer.train_epoch`` over its own loader
with the seeded weights. While it does, :class:`Recorder` notes what the step
programs were given and what they returned, dispatch by dispatch through the
first epoch, until three optimizer steps AND the first ``train_multi``
dispatch are covered; through the window it keeps every step's own graph
count. After the window the plain reference rebuilds the recorded batches
from the RAW graphs (its own edges, its own degree statistics), follows the
same steps in float32 with its own AdamW, and :func:`compare` sets the two
side by side:

- ``graphs_gap``: worst relative gap, over the followed steps, between the
  number of graphs the step says entered its loss and the number the
  reference trained on (exact);
- ``window_graphs_gap``: the steps' graph counts summed over the WHOLE
  window against the numerator of the rate, the raw graphs of an epoch times
  the epochs (exact: a graph the loader dropped is not trained);
- ``loss_gap``: worst relative gap of a step's loss over the first 3 steps;
- ``grad_gap``: the first gradient as the optimizer got it (Adam's first
  moment after the first dispatch), worst leaf, gap of norms over the larger
  of the reference's norm of that leaf and of its median leaf;
- ``update_gap``: the parameters' change over the first 3 steps, same
  measure, over the leaves whose reference gradient is not nought to rounding
  (at least a thousandth of the median leaf's);
- ``update_gap_median``: the same per-leaf gaps, their median instead of
  their worst (steady where one small leaf's change is noise: PERF.md
  section 2);
- ``multi_loss_gap``, ``multi_update_gap``, ``multi_update_gap_median``: the
  same three over the steps of the first ``train_multi`` dispatch alone: its
  steps' losses, and the parameters' change from the state it was given to
  the state it returned.

A cell's limits file (``limits/<cell>.json``, set from chip readings:
PERF.md section 2) names which of these are compared for it; a number it
names that a run cannot give is not correct.
"""

import importlib
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
STEPS = 3  # optimizer steps whose loss, gradient and update are compared


def load_reference(model_type):
    return importlib.import_module(f"reference.{model_type}")


def find_adam(opt_state):
    """The node of an optax state that holds Adam's moments."""
    stack = [opt_state]
    while stack:
        node = stack.pop()
        if hasattr(node, "mu") and hasattr(node, "nu"):
            return node
        if isinstance(node, (tuple, list)):
            stack.extend(node)
        elif isinstance(node, dict):
            stack.extend(node.values())
    raise RuntimeError("no Adam moments in the optimizer state")


class Recorder:
    """Wraps the trainer's two step programs, for the whole run.

    Through the first epoch (until :meth:`fetch`) it notes, per dispatch and
    until ``STEPS`` optimizer steps and the first ``train_multi`` dispatch
    are covered: the targets and graph sizes the program was fed (to find
    the raw graphs again), the per-step losses and graph counts it returned,
    and copies of the parameters and of Adam's first moment after it.
    Through the window (from :meth:`count_window`) it keeps each dispatch's
    ``num_graphs`` on the device: no readback, one list append a dispatch."""

    def __init__(self, trainer):
        import jax
        import jax.numpy as jnp

        self.dispatches = []  # dicts: multi, count, targets, sizes, losses, counts, params, mu
        self.covered = 0
        self.noting = True
        self.window_counts = None
        self._copy = jax.jit(
            lambda t: jax.tree_util.tree_map(jnp.copy, t)
        )
        steps = trainer._steps
        for name in ("train_step", "train_multi"):
            if getattr(steps, name) is not None:
                setattr(steps, name, self._wrap(name, getattr(steps, name)))

    def _wrap(self, name, fn):
        multi = name == "train_multi"

        def call(state, dev, rng):
            new_state, metrics = fn(state, dev, rng)
            if self.noting:
                self._note(multi, dev, new_state, metrics)
            elif self.window_counts is not None:
                self.window_counts.append(metrics["num_graphs"])
            return new_state, metrics

        return call

    def _note(self, multi, dev, state, metrics):
        import jax

        y_graph, y_node, n_node, gmask = jax.device_get(
            (dev.targets[0], dev.targets[1], dev.n_node, dev.graph_mask)
        )
        if not multi:
            y_graph, y_node = y_graph[None], y_node[None]
            n_node, gmask = n_node[None], gmask[None]
        count = n_node.shape[0]
        self.dispatches.append({
            "multi": multi, "count": count, "y_graph": y_graph,
            "y_node": y_node, "n_node": n_node, "graph_mask": gmask,
            "losses": metrics["loss"], "counts": metrics["num_graphs"],
            "params": self._copy(state.params),
            "mu": self._copy(find_adam(state.opt_state).mu),
        })
        self.covered += count
        if self.covered >= STEPS and any(d["multi"] for d in self.dispatches):
            self.noting = False

    def fetch(self):
        """Host copies of what the first epoch's dispatches noted (call it
        once that epoch has been read back); nothing more is noted."""
        import jax

        self.noting = False
        out = []
        for d in self.dispatches:
            d = dict(d)
            for key in ("losses", "counts"):
                d[key] = np.atleast_1d(
                    np.asarray(jax.device_get(d[key]), np.float64)
                )
            d["params"] = jax.device_get(d["params"])
            d["mu"] = jax.device_get(d["mu"])
            out.append(d)
        self.dispatches = []
        return out

    def count_window(self):
        self.window_counts = []

    def window_graphs(self):
        """Graphs the step programs say they trained on in the window."""
        import jax

        return float(sum(
            np.sum(np.asarray(c, np.float64))
            for c in jax.device_get(self.window_counts)
        ))


def graphs_of(dispatch, raw_graphs):
    """The raw graphs of each step of a dispatch, found again by the
    targets (the graph's, and its first atom's) in what the program was
    fed."""
    def key(y_graph, y_first):
        return np.asarray(y_graph, np.float32).tobytes() + np.asarray(
            y_first, np.float32).tobytes()

    index = {key(g["y_graph"], g["y_node"][0]): i
             for i, g in enumerate(raw_graphs)}
    if len(index) != len(raw_graphs):
        raise RuntimeError("two raw graphs share their targets")
    steps = []
    for k in range(dispatch["count"]):
        real = dispatch["graph_mask"][k]
        sizes = dispatch["n_node"][k][real]
        starts = np.cumsum(sizes) - sizes
        ids = [
            index[key(yg, dispatch["y_node"][k][s])]
            for yg, s in zip(dispatch["y_graph"][k][real], starts)
        ]
        picked = [raw_graphs[i] for i in ids]
        if [len(g["pos"]) for g in picked] != [int(s) for s in sizes]:
            raise RuntimeError("graph sizes fed differ from the raw graphs'")
        steps.append(picked)
    return steps


def largest_batch(graphs, degrees, batch_size):
    """(nodes, edges, graphs) no batch of ``batch_size`` graphs can pass:
    the sums over the largest ones. Every step of the reference is padded
    to it, so one shape compiles per cell, whatever the seed."""
    nodes = np.sort([len(g["pos"]) for g in graphs])[-batch_size:].sum()
    edges = np.sort([int(d.sum()) for d in degrees])[-batch_size:].sum()
    return int(nodes), int(edges), int(batch_size)


def follow(ref, ref_params, arch, stats, step_graphs, lr, shape,
           rounding="f32", drop_half=False):
    """The reference's own training over ``step_graphs`` (a list of lists
    of raw graphs), each padded to ``shape``: per-step losses and graph
    counts, and the point (params, mu) after every step. ``drop_half``
    plants the fault 'half of the batch left out, the mean taken over the
    rest' into the reference."""
    import jax
    import jax.numpy as jnp

    from reference import common as C

    def step(params, mu, nu, batch, t):
        (loss, _), grads = jax.value_and_grad(
            lambda p: ref.loss_fn(p, batch, arch, stats, rounding),
            has_aux=True,
        )(params)
        params, mu, nu = C.adamw_step(params, mu, nu, grads, t, lr)
        return params, mu, nu, loss

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)  # noqa: E731
    params = jax.tree_util.tree_map(jnp.array, ref_params)
    mu, nu = zeros(params), zeros(params)
    losses, counts, points = [], [], {}
    for t, graphs in enumerate(step_graphs, start=1):
        if drop_half:
            graphs = graphs[: max(len(graphs) // 2, 1)]
        counts.append(len(graphs))
        batch = C.assemble(graphs, arch["radius"], arch["max_neighbours"], shape)
        params, mu, nu, loss = step(params, mu, nu, batch, jnp.float32(t))
        losses.append(float(loss))
        points[t] = jax.device_get((ref.to_program(params),
                                    ref.to_program(mu)))
    return {"losses": np.asarray(losses),
            "counts": np.asarray(counts, np.float64), "points": points}


def _leaf_norms(tree):
    import jax

    flat = jax.tree_util.tree_leaves_with_path(tree)
    return {
        jax.tree_util.keystr(p): float(
            np.linalg.norm(np.asarray(l, np.float64))
        )
        for p, l in flat
    }


def _delta(after, before):
    import jax

    return jax.tree_util.tree_map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        after, before,
    )


def leaf_gaps(prog, ref, keep=None):
    """Per leaf, |‖prog‖ - ‖ref‖| / max(‖ref‖, median ‖ref‖)."""
    floor = float(np.median(list(ref.values())))
    return {
        name: abs(prog[name] - r) / max(r, floor, 1e-300)
        for name, r in ref.items() if keep is None or name in keep
    }


def worst_leaf_gap(prog, ref, keep=None):
    gaps = leaf_gaps(prog, ref, keep)
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def compare(program, reference, start_params, marks):
    """The numbers compared. ``program`` and ``reference`` are dicts with
    ``losses`` and ``counts`` (per step) and ``points`` ({steps made:
    (parameters, Adam's first moment)}); ``start_params`` the seeded weights
    both started from; ``marks`` the points the program's dispatches expose
    (:func:`program_side`)."""
    def gap(key, steps):
        p, r = program[key][steps], reference[key][steps]
        return float(np.max(np.abs(p - r) / np.abs(r)))

    def params_at(side, n):
        return start_params if n == 0 else side["points"][n][0]

    def update_gaps(lo, hi):
        u_ref = _leaf_norms(_delta(params_at(reference, hi), params_at(reference, lo)))
        u_prog = _leaf_norms(_delta(params_at(program, hi), params_at(program, lo)))
        worst, leaf = worst_leaf_gap(u_prog, u_ref, keep)
        median = float(np.median(list(leaf_gaps(u_prog, u_ref, keep).values())))
        return worst, median, leaf

    g_ref = _leaf_norms(reference["points"][marks["first"]][1])
    g_prog = _leaf_norms(program["points"][marks["first"]][1])
    grad_gap, grad_leaf = worst_leaf_gap(g_prog, g_ref)
    floor = 1e-3 * float(np.median(list(g_ref.values())))
    keep = {k for k, v in g_ref.items() if v >= floor}
    update_gap, update_median, update_leaf = update_gaps(0, marks["three"])
    numbers = {
        "graphs_gap": gap("counts", slice(None)),
        "loss_gap": gap("losses", slice(0, STEPS)),
        "grad_gap": grad_gap,
        "update_gap": update_gap,
        "update_gap_median": update_median,
    }
    notes = {"grad_leaf": grad_leaf, "update_leaf": update_leaf,
             "left_out": sorted(set(g_ref) - keep)}
    if marks["multi"] is not None:
        lo, hi = marks["multi"]
        worst, median, notes["multi_update_leaf"] = update_gaps(lo, hi)
        numbers.update(multi_loss_gap=gap("losses", slice(lo, hi)),
                       multi_update_gap=worst, multi_update_gap_median=median)
    return numbers, dict(notes, numbers=numbers)


def program_side(dispatches):
    """The program's readings at the points its dispatches expose, and
    those points: after the first dispatch, after the first one that covers
    ``STEPS``, and before and after the first ``train_multi`` dispatch."""
    points, marks, made = {}, {"multi": None}, 0
    for d in dispatches:
        if d["multi"] and marks["multi"] is None:
            marks["multi"] = (made, made + d["count"])
        made += d["count"]
        points[made] = (d["params"], d["mu"])
        marks.setdefault("first", made)
        if made >= STEPS:
            marks.setdefault("three", made)
    return {
        "losses": np.concatenate([d["losses"] for d in dispatches]),
        "counts": np.concatenate([d["counts"] for d in dispatches]),
        "points": points,
    }, marks


def load_limits(cell_name, files=HERE):
    with open(os.path.join(files, "limits", cell_name + ".json")) as f:
        return json.load(f)["limits"]


def verdict(numbers, limits):
    """(correct, {name: {"value", "limit"}}); a number that is not finite,
    or that the run could not give, is not correct."""
    report, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        if value is not None and not np.isfinite(value):
            value = None  # JSON has no NaN
        report[name] = {"value": value, "limit": limit}
        if value is None or value > limit:
            ok = False
    return ok, report
