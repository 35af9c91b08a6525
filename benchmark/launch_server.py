"""The benchmark's server child: the program's normal entry point, plus a
way to profile the device it holds.

    python benchmark/launch_server.py --control-fd R --answer-fd W -- <server flags>

calls ``redisson_tpu.server.server.main(<server flags>)`` on the main thread
— the entry point ``python -m redisson_tpu.server`` uses, flags as a user
passes them — after starting one daemon thread that reads commands from the
control pipe and answers each with one JSON line:

  trace-start <dir>   jax.profiler.start_trace(dir), then one host
                      annotation ("bench.mark") whose wall-clock time is in
                      the answer: it fixes the trace clock against the wall
  trace-stop          jax.profiler.stop_trace(); the answer says when, on
                      the wall clock, the stop was asked for

Only the process that holds the chip can trace it, and the benchmark's
parent must stay off jax (one process per chip), so the tracing has to live
here; it is the whole reason this launcher exists.  With ``--trace 0`` the
thread idles on its pipe: both kinds of run start the server the same way.
"""
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _control(control_fd: int, answer_fd: int) -> None:
    with os.fdopen(control_fd, "r") as commands, os.fdopen(answer_fd, "w") as answers:
        for line in commands:
            words = line.split()
            try:
                import jax

                from benchmark.reduce_trace import MARK  # the name it looks for

                if words[0] == "trace-start":
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0  # device and annotations only
                    options.host_tracer_level = 1
                    jax.profiler.start_trace(words[1], profiler_options=options)
                    with jax.profiler.TraceAnnotation(MARK):
                        mark = time.time_ns()
                        time.sleep(0.001)
                    out = {"ok": True, "mark_wall_ns": mark}
                elif words[0] == "trace-stop":
                    asked = time.time_ns()
                    jax.profiler.stop_trace()
                    out = {"ok": True, "stop_wall_ns": asked,
                           "stop_took_s": (time.time_ns() - asked) / 1e9}
                else:
                    out = {"ok": False, "error": f"unknown command {words[0]!r}"}
            except Exception as e:  # noqa: BLE001 — the parent decides
                out = {"ok": False, "error": f"{type(e).__name__}: {e}"}
            answers.write(json.dumps(out) + "\n")
            answers.flush()


def main(argv) -> int:
    if "--" not in argv:
        print(__doc__, file=sys.stderr)
        return 2
    mine, server_argv = argv[: argv.index("--")], argv[argv.index("--") + 1:]
    fds = dict(zip(mine[0::2], mine[1::2]))
    sys.path.insert(0, ROOT)
    threading.Thread(
        target=_control, daemon=True, name="bench-control",
        args=(int(fds["--control-fd"]), int(fds["--answer-fd"])),
    ).start()
    from redisson_tpu.server.server import main as server_main

    return server_main(server_argv) or 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
