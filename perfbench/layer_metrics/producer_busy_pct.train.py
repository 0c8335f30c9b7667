"""The busiest producer thread's share of the window: the time its spans
cover less its waits on a full or an empty queue (``queue_put_wait``,
``queue_get_wait``). Near 100 with the epoch loop waiting, one stage sets
the pace; far under it, the stages are not the limit."""

import span_window


def read(run):
    win = span_window.window_spans(run)
    if win is None:
        return None
    busy = span_window.busy_by_thread(win)
    if not busy:
        return None
    return 100.0 * max(busy.values()) / win["seconds"]
