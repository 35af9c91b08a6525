"""Host: the share of the window the server's event loop spent outside
``select`` — running callbacks, or holding a turn open while it waited for
the interpreter lock: 100 x busy seconds / uptime, both after minus before.
Near 100 the loop never sleeps and every hand-off to it queues."""
from benchmark import loop_account


def read(obs):
    return loop_account.per(obs, loop_account.BUSY_S, loop_account.UPTIME_S, 100.0)
