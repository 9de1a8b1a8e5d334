"""Device time per training step in the flash-attention kernels, in ms:
the operations whose instruction name starts with ``flash_attention``
(``flash_attention_fwd``, ``flash_attention_dq``, ``flash_attention_dkv``),
over the traced ``train`` steps. What ``attention_ms.train`` holds beyond
it is the projections, rotary embedding and layout. None where no such
operation ran: a program whose attention is not these kernels."""

from bench import scopes as S
from bench import trace as T

PREFIX = "flash_attention"


def _is_kernel(event) -> bool:
    # "%flash_attention_fwd.3 = (...) custom-call(...)" or the bare name
    return event.name.partition(" = ")[0].lstrip("%").startswith(PREFIX)


def read(run):
    t, ev = run.get("trace"), run.get("events")
    steps = S.train_steps(run)
    if not t or not ev or not steps:
        return None
    seconds = T.time_in(ev["devices"], t["window_ns"], _is_kernel)
    return 1e3 * seconds / steps if seconds > 0 else None
