"""A device call as the timeline sees it: the first call of a jitted
program (its compile) and every later one (its dispatch), an upload, a
fetch. Each books through `utils/timeline.boundary`, which owns what a
span feeds (ring event, statement-trace phase, metric series); the cop
engine books under `device.`, the MPP engine under `mpp.`.
"""

from __future__ import annotations

import time

import numpy as np

from ..jaxenv import jax, jnp
from ..utils import memory as _mem
from ..utils import timeline as TL


class Timed:
    """A jitted program with its first dispatch timed: JAX traces+compiles
    synchronously inside the first call (later calls dispatch async in
    sub-ms), so the first-call wall IS the compile cost — booked as
    `<prefix>.compile` (the tidb_tpu_compile_seconds series and the
    trace's compile phase); every later call is booked as
    `<prefix>.dispatch`: the jit call IS the async dispatch — its wall
    is queueing cost, not compute (the fetch observes that). A benign
    race (two threads both timing the first call) at worst records one
    extra sample."""

    __slots__ = ("fn", "_compiled", "_compile", "_dispatch")

    def __init__(self, fn, prefix: str = "device"):
        self.fn = fn
        self._compiled = False
        self._compile = prefix + ".compile"
        self._dispatch = prefix + ".dispatch"

    def __call__(self, *args):
        t0 = time.perf_counter_ns()
        out = self.fn(*args)
        t1 = time.perf_counter_ns()
        if self._compiled:
            TL.boundary(self._dispatch, t0, t1)
        else:
            self._compiled = True
            TL.boundary(self._compile, t0, t1)
        return out


def to_device(a: np.ndarray, device=None):
    """Host→device upload with transfer accounting (`device.h2d`: the h2d
    half of tidb_tpu_transfer_bytes_total, the upload stage of
    tidb_tpu_tile_build_seconds and the trace's device.transfer phase).
    With `device` the array is COMMITTED to that mesh device — jit
    follows committed inputs, so pinning the uploads is what pins the
    whole launch to its runner lane (PR 6 per-device dispatch).
    The bytes also consume into the bound statement MemTracker — device
    allocations were invisible to memory quotas before PR 4 — so the
    consume can raise the quota/server-limit error right at the
    allocation site (a real allocation failure, never a device fault)."""
    _mem.consume_current(a.nbytes)
    with TL.span("device.h2d", bytes=int(a.nbytes)):
        return jnp.asarray(a) if device is None else jax.device_put(a, device)


def tree_to_device(tree, device=None):
    """Upload every leaf of a codec payload pytree (dict of numpy arrays)
    through `to_device`, so transfer accounting/quota charges cover the
    compressed form — the only form that crosses the wire."""
    return jax.tree_util.tree_map(lambda a: to_device(a, device), tree)


def fetch(x, programs: int = 1):
    """Device→host fetch: `jax.device_get` blocks until the async dispatch
    finishes computing, so the wall of `device.execute` is the HOST
    blocked in `device_get` for the `programs` dispatched programs of
    the launch: the observable device execute+fetch time
    (tidb_tpu_device_execute_seconds); result bytes are the d2h half of
    the transfer series."""
    t0 = time.perf_counter_ns()
    out = jax.device_get(x)
    t1 = time.perf_counter_ns()
    nbytes = sum(getattr(l, "nbytes", 0) for l in jax.tree_util.tree_leaves(out))
    TL.boundary("device.execute", t0, t1, d2h_bytes=int(nbytes), programs=programs)
    # NOT consumed into the memory tracker: the fetched result becomes a
    # chunk that drain() charges at materialization — charging the d2h
    # here too would double-count the same data on the device path only
    return out
