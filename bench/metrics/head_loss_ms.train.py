"""Device time per training step under ``head_loss``, in ms: the final
norm, the unembedding and the chunked cross-entropy."""

from bench import scopes as S

CHECKOUT = S.checkout_of(__file__)


def read(run):
    return S.scope_ms(run, CHECKOUT, ("head_loss",), S.train_steps(run))
