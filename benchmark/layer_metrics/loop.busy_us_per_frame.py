"""Host: microseconds of the event loop's busy time a frame served, over the
window.  Its inverse is the most frames a second one loop can serve."""
from benchmark import loop_account


def read(obs):
    return loop_account.per(obs, loop_account.BUSY_S, loop_account.FRAMES, 1e6)
