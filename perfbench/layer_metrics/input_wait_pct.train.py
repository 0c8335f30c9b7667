"""Share of the window the epoch loop spent waiting for a batch: the seconds
``Trainer._prefetch_put`` reported to the goodput ledger's ``data_wait``,
over the window's seconds (host clock)."""


def read(run):
    return 100.0 * run["data_wait_s"] / run["window"]["window_s"]
