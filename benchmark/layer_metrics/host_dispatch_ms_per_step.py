"""Train-loop layer: host milliseconds per step inside the Engine's
``dispatch`` span (handing one step to the runtime)."""


def reduce(run: dict):
    spans = [e["dur"] for e in run["spans"] if e["name"] == "dispatch"]
    return sum(spans) / 1e3 / run["steps"] if spans else None
