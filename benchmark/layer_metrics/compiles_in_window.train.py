"""Layer: model + kernels. Backend compiles (and cache loads of a new
program) the trainer' jax.monitoring listener saw after the window
opened. Should be 0: the step compiled at step 1, in set-up. Source: program
counter (the launcher's listener)."""


def read(run):
    return float(sum(len(a["compiles_in_window"]) for a in run["launcher"]))
