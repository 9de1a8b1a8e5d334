"""Device time per training step in the feed-forward layers, in ms: under
the dense ``mlp`` scope or the routed experts' ``moe/experts``."""

from bench import scopes as S

CHECKOUT = S.checkout_of(__file__)


def read(run):
    return S.scope_ms(run, CHECKOUT, ("mlp", "moe/experts"), S.train_steps(run))
