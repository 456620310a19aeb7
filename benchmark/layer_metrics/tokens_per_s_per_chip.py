"""Train-loop layer: tokens of all steps completed in the measured window over
its seconds, per chip (steps x sequences a step x sequence length / window
seconds; host clock; the window opens and closes on a hard sync). A token
cell's throughput in its own unit, until a benchmark PR makes it an
end-to-end metric."""


def reduce(run: dict):
    if not run.get("lm"):
        return None
    return run["steps"] * run["batch_per_chip"] * run["lm"]["seq_len"] \
        / run["window_s"]
