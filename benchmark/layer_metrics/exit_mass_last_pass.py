"""Graph layer: the share of the exit distribution's mass, mean over tokens
and over the window's displays, that the looped LM's last pass takes (what
is left after every gate; the EXIT_LOSS layer's scalar tops in the Engine's
metric rows). 12.5% for four passes and gates at 1/2; the entropy bonus
holds it away from 0 and 100."""


def reduce(run: dict):
    mass = (run.get("lm") or {}).get("exit_mass")
    if not mass:
        return None
    return 100.0 * sum(m[-1] for m in mass) / len(mass)
