"""Device time per training step in the optimizer, in ms: under
``optimizer`` (AdamW and the orthogonal partition with its kernels) or
``apply_updates``."""

from bench import scopes as S

CHECKOUT = S.checkout_of(__file__)


def read(run):
    return S.scope_ms(run, CHECKOUT, ("optimizer", "apply_updates"),
                      S.train_steps(run))
