"""Engine scheduler: share of the traced window's device-idle seconds under no leaf span of the
scheduler: between passes, or in a pass and outside every phase."""
import tick_readers


def read(obs):
    return tick_readers.gap_unnamed_share(obs)
