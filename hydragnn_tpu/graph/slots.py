"""Host buffers the input pipeline owns and gets back.

A collated batch is a dozen padded arrays and as many temporaries, 30-60 MB
at the benchmark's PNA cell; glibc hands blocks of that size back to the
kernel when they are freed, so a batch made of fresh arrays faults every one
of its pages in again (~4 us a page on the chip's host: half of a collate,
PERF.md section 6, PR 31 and PR 33). A :class:`Slot` is the set of arrays
one batch (or one group's stack) is made of, by name; a :class:`SlotPool`
hands slots out per shape key and takes them back from whoever read them
last.

The pool references FREE slots only. A slot that is handed out and never
released is simply forgotten (the garbage collector frees it with its last
reader), so a consumer that keeps its batches keeps arrays nobody rewrites,
and an interrupted epoch leaves nothing marked busy. Slots are made when an
``acquire`` finds none free: never more than are alive at once.
"""

import threading
import weakref

import numpy as np


def filled(slot, name, shape, dtype, fill=0, start=0):
    """An array of ``shape`` and ``dtype`` holding ``fill`` everywhere
    (``None``: whatever it held): ``slot``'s array of that name, reset, or
    a fresh one where there is no slot. With ``start``, only the rows from
    ``start`` on hold ``fill``, and those before it are the caller's to
    write (a batch's head, written once). The ONE allocation site of the
    collate path's large arrays."""
    if start:
        a = filled(slot, name, shape, dtype, None)
        a[start:] = fill
        return a
    if slot is not None:
        return slot.array(name, shape, dtype, fill)
    if fill is None:
        return np.empty(shape, dtype)
    if not fill:
        return np.zeros(shape, dtype)
    return np.full(shape, fill, dtype)


class Slot:
    """The arrays of one batch, by name. ``state`` says where it came from
    at its last ``acquire``: ``"made"`` or ``"reused"``."""

    __slots__ = ("key", "state", "pool", "_arrays", "__weakref__")

    def __init__(self, pool, key):
        self.key = key
        self.state = "made"
        self.pool = pool
        self._arrays = {}

    def array(self, name, shape, dtype, fill=None, make=None):
        """The slot's array called ``name``; made on first use (by
        ``make()`` where given: a constant table) and again where shape or
        dtype changed. ``fill`` resets it: a reused array then holds
        exactly what a fresh one would."""
        shape = tuple(shape)
        a = self._arrays.get(name)
        if a is None or a.shape != shape or a.dtype != dtype:
            a = self._arrays[name] = (
                np.empty(shape, dtype) if make is None else make()
            )
        if fill is not None:
            a.fill(fill)
        return a

    @property
    def nbytes(self):
        return sum(a.nbytes for a in self._arrays.values())

    def release(self, forget=()):
        """Back to the pool: its arrays may be rewritten from now on.
        Those among ``forget`` (by identity) have found another owner (a
        device array that aliases the buffer) and leave the slot first:
        the next use makes new ones in their place."""
        if forget:
            self._arrays = {
                name: a for name, a in self._arrays.items()
                if not any(a is f for f in forget)
            }
        self.pool.release(self)


class SlotPool:
    """Free slots per key, behind a lock (collate threads acquire, the put
    stage releases). ``reused`` and ``made`` count acquisitions since the
    pool was made."""

    def __init__(self):
        self._free = {}
        self._lock = threading.Lock()
        self._alive = weakref.WeakSet()  # every slot not yet collected
        self.reused = 0
        self.made = 0

    def acquire(self, key):
        with self._lock:
            free = self._free.get(key)
            if free:
                slot = free.pop()
                slot.state = "reused"
                self.reused += 1
            else:
                slot = Slot(self, key)
                self._alive.add(slot)
                self.made += 1
        return slot

    def release(self, slot):
        with self._lock:
            free = self._free.setdefault(slot.key, [])
            if not any(s is slot for s in free):
                free.append(slot)

    def counts(self):
        """``{"reused", "made", "bytes"}``: acquisitions so far, and the
        host memory of every slot still alive (free or with a reader)."""
        with self._lock:
            return {
                "reused": self.reused,
                "made": self.made,
                "bytes": sum(s.nbytes for s in list(self._alive)),
            }
