"""Input layer: what ONE batch's copy gets of the host link with the link
to itself: the batch's ``bytes`` over its ``producer_h2d`` +
``producer_h2d_land`` (the ``device_put`` calls and the wait until the
arrays are ready; the program waits for a sample of the batches, and for
the batch before each so that nothing else is on the link), mean over the
window's sampled batches, in GB/s. Copies that overlap, as an untraced
run's do, can sustain more than this. Left out where the program records
no ``producer_h2d_land``."""

import host_spans


def reduce(run: dict):
    took, size = {}, {}
    for name in ("producer_h2d", "producer_h2d_land"):
        for e in host_spans.named(run, name):
            args = e.get("args") or {}
            if e.get("ph") != "X" or "bytes" not in args:
                continue
            took.setdefault(args["batch"], {})[name] = e["dur"]
            size[args["batch"]] = args["bytes"]
    rates = [size[b] / 1e9 / (sum(t.values()) / 1e6)
             for b, t in took.items() if len(t) == 2 and sum(t.values()) > 0]
    return sum(rates) / len(rates) if rates else None
