"""Input layer: share of the measured window the train thread spent in the
Engine's ``prefetch_wait`` span, waiting for the next batch."""


def reduce(run: dict):
    spans = [e["dur"] for e in run["spans"] if e["name"] == "prefetch_wait"]
    return 100.0 * sum(spans) / 1e6 / run["window_s"] if spans else None
