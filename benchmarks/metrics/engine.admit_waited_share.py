"""Engine scheduler: of the requests admitted in the traced window, the share that a pass of the
scheduler had seen and left waiting: the ``admit`` mark's ``waited_for`` is ``slot``, ``blocks`` or
``adapter`` and not ``tick``. The log line gives the count by cause."""
import cause_readers


def read(obs):
    return cause_readers.admit_waited_share(obs)
