"""Graph layer: tokens at the fullest expert over the mean, from the step's
own routing as the MOE layers publish it per display (mean over the
window's displays and layers); 1.0 = balanced. The grouped matmul's tiles
are sized by the fullest group."""


def reduce(run: dict):
    load = (run.get("lm") or {}).get("expert_load")
    return sum(load) / len(load) if load else None
