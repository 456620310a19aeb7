"""Entry layer: seconds from the OS's start of the process to the program's
``cli_setup`` (``startup.timeline.cli_setup.at_s``, the recorder's own clock):
what the harness does before it calls ``cli.main`` — imports, the backend's
start (``device.require``), data generation, the job's files. The first
unnamed end of ``setup_s``. Since PR 63 ``setup_s`` leaves the backend's start
(``backend_start_s``) out; this reading still holds it."""

import startup_spans


def reduce(run: dict):
    row = startup_spans.timeline(run, "cli_setup")
    return None if row is None else float(row["at_s"])
