"""Host: the CPU the whole server process burnt over the window — the loop,
the workers, and the runtime's and XLA's own threads — as a share of the wall
clock; may pass 100 (a core is 100).  Far above loop + workers, the
runtime's threads are who holds the host."""
from benchmark import loop_account


def read(obs):
    return loop_account.per(obs, loop_account.PROCESS_CPU_S, loop_account.UPTIME_S, 100.0)
