"""Host: the CPU the event loop's thread burnt over the window, as a share
of the wall clock (its own CPU clock over uptime, after minus before).
``loop.busy_share`` less this is time the loop held a turn open without
running: the interpreter lock, or a blocking call."""
from benchmark import loop_account


def read(obs):
    return loop_account.per(obs, loop_account.LOOP_CPU_S, loop_account.UPTIME_S, 100.0)
