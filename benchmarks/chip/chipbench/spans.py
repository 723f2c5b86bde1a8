"""Host spans around the program's layers, for the traced run only.

:func:`install` wraps the calls into each layer in a
``jax.profiler.TraceAnnotation`` named after the call, so the trace shows
what the host was doing in every idle gap of the device, and records what
each forest launch and each bank wave carried (rows, groups touched,
traversal steps), from which the forest kernel's roofline and the bank's
model FLOPs are worked out. Nothing is wrapped in an untraced run.
:func:`uninstall` restores the originals.
"""
from __future__ import annotations

import functools
import inspect
import time

import numpy as np

# most specific first: an idle instant goes to the first one active
HOST_SPANS = ("forest_eval.predict_grouped", "ModelBank._dnn_member",
              "ModelBank.interpolate", "ModelBank.execute",
              "LatencyOracle.plan", "LatencyService.run_once",
              "TransportServer._dispatch")


class Records:
    def __init__(self):
        self.forest = []   # (t0, t1, rows, groups touched (gid array), steps)
        self.bank = []     # (t0, t1, rows, forest steps)


def _sync(obj, attr, name, after=None):
    import jax
    orig = getattr(obj, attr)
    sig = inspect.signature(orig)

    @functools.wraps(orig)
    def wrapped(*a, **kw):
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(name):
            out = orig(*a, **kw)
        if after is not None:
            after(t0, time.monotonic(), sig.bind(*a, **kw).arguments)
        return out
    setattr(obj, attr, wrapped)
    return orig


def _async(obj, attr, name):
    import jax
    orig = getattr(obj, attr)

    @functools.wraps(orig)
    async def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return await orig(*a, **kw)
    setattr(obj, attr, wrapped)
    return orig


def install(records: Records):
    """Wrap the layer calls; returns what :func:`uninstall` needs."""
    from repro.api.bank import ModelBank
    from repro.api.oracle import LatencyOracle
    from repro.kernels import forest_eval
    from repro.serve.latency_service import LatencyService
    from repro.serve.transport import TransportServer

    # arguments are read by name: a call that no longer matches fails the
    # run, and a launch that bypasses these calls is caught by the bank's
    # own counters (``harness``) and the trace (``forest_roofline.advise``)
    def forest_done(t0, t1, args):
        gid = np.asarray(args["gid"])
        depth = np.asarray(args["depth"])
        T = np.shape(args["feat"])[1]
        records.forest.append((t0, t1, len(gid), np.unique(gid),
                               int(T * depth[gid].sum())))

    def bank_done(t0, t1, args):
        bank, gids = args["self"], np.asarray(args["gids"])
        steps = 0
        if bank.forest is not None:
            T = bank.forest["feat"].shape[1]
            steps = int(T * bank.forest["depth"][gids].sum())
        records.bank.append((t0, t1, len(gids), steps))

    saved = [
        (forest_eval, "predict_grouped",
         _sync(forest_eval, "predict_grouped",
               "forest_eval.predict_grouped", forest_done)),
        (ModelBank, "execute",
         _sync(ModelBank, "execute", "ModelBank.execute", bank_done)),
        (ModelBank, "_dnn_member",
         _sync(ModelBank, "_dnn_member", "ModelBank._dnn_member")),
        (ModelBank, "interpolate",
         _sync(ModelBank, "interpolate", "ModelBank.interpolate")),
        (LatencyOracle, "plan",
         _sync(LatencyOracle, "plan", "LatencyOracle.plan")),
        (LatencyService, "run_once",
         _sync(LatencyService, "run_once", "LatencyService.run_once")),
        (TransportServer, "_dispatch",
         _async(TransportServer, "_dispatch", "TransportServer._dispatch")),
    ]
    return saved


def uninstall(saved) -> None:
    for obj, attr, orig in saved:
        setattr(obj, attr, orig)
