"""Real (unpadded) graphs whose update completed in the window over the
window's wall time: whole epochs, with their boundaries, loader stalls and
each epoch's readback. ``correct`` holds the numerator to the graph counts
of the window's own steps (``window_graphs_gap``)."""


def read(run):
    return run["window"]["graphs"] / run["window"]["window_s"]
