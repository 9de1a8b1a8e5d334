"""Device idle time per training step inside the loop's ``train.batch``
spans, in ms: the part of the gap between steps spent making the batch."""

from bench import scopes as S


def read(run):
    idle, steps = S.idle_in(run, "train.batch"), S.train_steps(run)
    return 1e3 * idle / steps if idle is not None and steps else None
