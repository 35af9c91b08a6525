"""The server's always-on account of its event loop and of the host's CPU,
as two METRICS scrapes give it (``redisson_tpu/observe/trace.py
LoopSelector``, the gauges in ``server/server.py``): the series' names, and
the one piece of arithmetic their readers share.  Every series is a total
that only grows, so a window's share of one is after minus before
(``benchmark/counters.py``)."""
from benchmark import counters

TURNS = "rtpu_host_loop_turns_total"          # times the loop came back to select
BUSY_S = "rtpu_host_loop_busy_seconds_total"  # seconds it spent outside select
FRAMES = "rtpu_frames_served_total"           # non-empty frames the read loops served
LOOP_CPU_S = "rtpu_host_loop_cpu_seconds_total"        # the loop thread's CPU clock
WORKER_CPU_S = "rtpu_host_worker_cpu_seconds_total"    # the fixed pools' threads'
PROCESS_CPU_S = "rtpu_host_process_cpu_seconds_total"  # the whole process's
UPTIME_S = "rtpu_host_uptime_seconds_total"   # the wall clock beside them


def per(obs, over: str, under: str, scale: float = 1.0):
    """``scale`` x what the window added to ``over``, for each unit it added
    to ``under``; None where the program lacks either series or the window
    added nothing to ``under``."""
    a, b = counters.delta(obs, over), counters.delta(obs, under)
    if a is None or not b:
        return None
    return scale * a / b
