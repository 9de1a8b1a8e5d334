"""Device time per orthoptimizer step under ``apply_updates``, in ms: the
matrices plus their updates."""

from bench import scopes as S

CHECKOUT = S.checkout_of(__file__)


def read(run):
    return S.scope_ms(run, CHECKOUT, ("apply_updates",), run.get("steps_traced"))
