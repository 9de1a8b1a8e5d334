"""Share of the traced training window's device time (its leaf operations)
that lies under some scope of the yardstick's vocabulary, in percent: the
guard that the per-scope times cover the step."""

from bench import scopes as S

CHECKOUT = S.checkout_of(__file__)


def read(run):
    by = S.by_scope(run, CHECKOUT)
    total = sum(by.values()) if by else 0.0
    scoped = sum(v for k, v in by.items() if k) if by else 0.0
    return 100.0 * scoped / total if scoped > 0 else None
