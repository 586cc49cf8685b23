"""Engine scheduler: 95th percentile of submit -> admitted to a slot, from the
engine's own per-request timeline (``Request.timeline``), over the requests due
in the window."""
import readers


def read(obs):
    return readers.queue_wait_p95_ms(obs)
