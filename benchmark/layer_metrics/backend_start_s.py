"""Device layer: seconds the runtime took to hand the chips over, the one
call ``jax.devices()`` inside ``device.require`` (the process's first touch
of the backend; the benchmark's clock). Nothing of the repository runs inside
it, and identical processes on one machine read 5.6-10.3 s for it (PERF.md,
section 6, PR 63), so ``run.py`` takes it OUT of ``setup_s`` and reports it
here: ``setup_s`` + this = the top of ``run.py`` -> the window's first step,
which is what ``setup_s`` was until PR 63. It lies inside
``setup_before_program_s``, which still counts it."""


def reduce(run: dict):
    value = run.get("backend_start_s")
    return None if value is None else float(value)
