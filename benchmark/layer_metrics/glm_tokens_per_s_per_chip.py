"""Train-loop layer: tokens of all steps completed in the measured window over
its seconds, per chip (steps x sequences a step x sequence length / window
seconds; host clock). ``tokens_per_s_per_chip`` under a name of this cell's
own."""


def reduce(run: dict):
    if not run.get("lm"):
        return None
    return run["steps"] * run["batch_per_chip"] * run["lm"]["seq_len"] \
        / run["window_s"]
