"""Entry layer: seconds from the end of the program's first step
(``startup.timeline.first_step``: ``at_s + dur_s``) to the opening of the
measured window (the run's ``setup_s``, the benchmark's clock): the
warm-up steps to the timed display, the resident masters, the settle
displays. The second unnamed end of ``setup_s``; the two clocks start a
few tenths of a second apart (``run.py``'s first line against the OS's
process start), which stays in the sum's tolerance."""

import startup_spans


def reduce(run: dict):
    row = startup_spans.timeline(run, "first_step")
    if row is None or run.get("setup_s") is None:
        return None
    return float(run["setup_s"]) - (row["at_s"] + row["dur_s"])
