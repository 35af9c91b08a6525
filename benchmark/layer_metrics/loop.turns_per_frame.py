"""Host: turns of the server's event loop a frame served, over the window
(every connection's frames; the loop's timers and the scrapes ride along).
A frame alone is read, awaits its worker, wakes, is encoded and written:
about five; frames that share turns read less."""
from benchmark import loop_account


def read(obs):
    return loop_account.per(obs, loop_account.TURNS, loop_account.FRAMES)
