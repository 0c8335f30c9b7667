"""Padded node slots that hold no atom, over all node slots dispatched in
the window (``GraphLoader.epoch_padding_stats``, a count)."""


def read(run):
    w = run["window"]
    if not w["padded_rows"]:
        return None
    return 100.0 * (1.0 - w["real_rows"] / w["padded_rows"])
