"""Device idle time per training step inside the program's ``train`` step
spans (``train/loop.py``), in ms: the gap the loop leaves between steps."""

from bench import scopes as S


def read(run):
    idle, steps = S.idle_in(run, S.STEP_SPAN), S.train_steps(run)
    return 1e3 * idle / steps if idle is not None and steps else None
