"""Device time per training step, in ms per chip, in the collectives
whose op path lies under the orthogonal partition's ``ortho`` scope: the
one payload psum of each constraint group (``ortho/.../step/psum``) and
the reshards of the stacks into the group step's layout and back. None
where none ran (one chip) or the trace has no scopes."""

from bench import collectives
from bench import scopes as S

CHECKOUT = S.checkout_of(__file__)


def read(run):
    return collectives.scoped_collective_ms(run, CHECKOUT, "ortho")
