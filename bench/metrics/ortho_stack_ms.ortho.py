"""Device time per orthoptimizer step stacking leaves into groups and back,
in ms: under ``ortho/stack`` (gather and pad to the kernel's plan) or
``ortho/unstack`` (crop, the update as a difference, scatter)."""

from bench import scopes as S

CHECKOUT = S.checkout_of(__file__)


def read(run):
    return S.scope_ms(run, CHECKOUT, ("ortho/stack", "ortho/unstack"),
                      run.get("steps_traced"))
