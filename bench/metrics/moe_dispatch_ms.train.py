"""Device time per training step moving tokens to and from the experts, in
ms: under ``moe/route``, ``moe/dispatch`` and ``moe/combine``."""

from bench import scopes as S

CHECKOUT = S.checkout_of(__file__)


def read(run):
    return S.scope_ms(run, CHECKOUT, ("moe/route", "moe/dispatch", "moe/combine"),
                      S.train_steps(run))
