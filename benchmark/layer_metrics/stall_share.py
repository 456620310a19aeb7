"""Train-loop layer: share of the measured window lost against the pace of
its own fastest display interval (`display` steps between two hard syncs) —
1 - intervals x fastest / their sum. 0 for a loop that runs evenly; a stall
every so often (a starved reader, a collector pause, a snapshot) shows here
before it is large enough to read in images_per_s_per_chip."""


def reduce(run: dict):
    intervals = run["display_intervals_s"]
    if not intervals:
        return None
    return 100.0 * (1.0 - len(intervals) * min(intervals) / sum(intervals))
