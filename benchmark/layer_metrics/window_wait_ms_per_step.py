"""Train-loop layer: host milliseconds per step spent waiting for the device
(``dispatch_window`` back-pressure + ``hard_sync`` at display boundaries).
Close to the step time means the device sets the pace."""

WAITS = ("dispatch_window", "hard_sync")


def reduce(run: dict):
    spans = [e["dur"] for e in run["spans"]
             if e["name"] in WAITS and e.get("ph") == "X"]
    return sum(spans) / 1e3 / run["steps"] if spans else None
