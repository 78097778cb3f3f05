"""One of the set-up phases the harness times on the host's clock
(`import_s`, `build_s`, `first_step_s`, `warm_steps_s`), in seconds."""


def read(run, phase):
    return run["phases"].get(phase)
