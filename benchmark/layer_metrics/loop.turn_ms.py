"""Host: how long one turn of the server's event loop took — busy seconds
over turns, after minus before, in ms: what one hand-off to the loop
(``call_soon_threadsafe``, a task made ready, a socket turned readable)
waits on average when the loop never sleeps."""
from benchmark import loop_account


def read(obs):
    return loop_account.per(obs, loop_account.BUSY_S, loop_account.TURNS, 1e3)
