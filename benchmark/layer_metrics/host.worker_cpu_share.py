"""Host: the CPU the server's worker pools' threads burnt over the window,
summed, as a share of the wall clock.  With ``host.loop_cpu_share`` near 100
together, one interpreter lock is the serial host; above it, the workers run
native code side by side."""
from benchmark import loop_account


def read(obs):
    return loop_account.per(obs, loop_account.WORKER_CPU_S, loop_account.UPTIME_S, 100.0)
