"""What one latency sample of a window is.

Every request is timed (``loadgen.py``): a row is ``(idx, t_ref, t_send,
t_done, free_at, ops, ok)``, and a request's latency is ``t_done - t_ref``.
A traffic file says over what the judged latency (``req_p50_ms``,
``req_p95_ms``) is taken:

  no ``latency_over`` key    over requests: one sample an answered request.
  ``"latency_over": "cycle"``  over a connection's cycles: one sample a
      completed cycle, its wall time — the first frame's send to the last
      frame's decoded reply — divided by its frames.  For a client whose
      unit of work is several frames (a stream producer: adds that are
      acknowledged at enqueue, then the read that makes them count) this is
      the time a frame costs it; the per-frame median of such a client says
      which of its frames happened to queue.  The generator module says which
      frames end a cycle (``cycle_ends(params, ops)``).
"""
import numpy as np


def request_ms(rows: np.ndarray) -> np.ndarray:
    """Latency of every answered request, in the rows' order."""
    ok = rows[:, 6] == 1
    return (rows[ok, 3] - rows[ok, 1]) * 1e3


def cycle_ms(rows: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """One connection's rows in send order and the mask of those that end a
    cycle -> milliseconds a frame, one sample a completed cycle.  A cycle is
    the frames after the previous cycle's end up to and with the next marked
    frame.  The connection's last cycle is left out: the window's end cut it
    short and ``closing`` ended it.  So is a cycle with a failed request."""
    samples, start = [], 0
    for end in np.flatnonzero(ends)[:-1]:
        frames = rows[start:end + 1]
        if (frames[:, 6] == 1).all():
            samples.append((frames[-1, 3] - frames[0, 2]) * 1e3 / len(frames))
        start = end + 1
    return np.array(samples, np.float64)


def judged_ms(reports: list, params: dict, gen) -> np.ndarray:
    """The samples ``req_p50_ms`` and ``req_p95_ms`` are taken over."""
    over = params.get("latency_over")
    if over is None:
        per_conn = [request_ms(r["rows"]) for r in reports]
    elif over == "cycle":
        per_conn = [cycle_ms(r["rows"], gen.cycle_ends(params, r["rows"][:, 5]))
                    for r in reports]
    else:
        raise ValueError(f"latency_over {over!r}: the one value is 'cycle'; no key means requests")
    return np.concatenate(per_conn)
