"""Device time per training step under the model's ``attention`` scope, in
ms: projections, rotary embedding, attention itself and the output
projection, forward, backward and recomputed together."""

from bench import scopes as S

CHECKOUT = S.checkout_of(__file__)


def read(run):
    return S.scope_ms(run, CHECKOUT, ("attention",), S.train_steps(run))
