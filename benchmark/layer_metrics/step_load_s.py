"""Entry / compile-cache layer: seconds the Engine spent compiling or loading
its train step (``stats.yaml: compiled_step.seconds``). Moves setup_s."""


def reduce(run: dict):
    step = run["stats"]["sections"].get("compiled_step", {})
    return float(step["seconds"]) if "seconds" in step else None
