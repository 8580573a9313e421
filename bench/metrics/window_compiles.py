"""window_compiles: XLA compilations from the window's open to the end
of its last tick; warm-up should leave none.

The program counts compilations process-wide
(``repro.obs.metrics.compile_times``, on the engine's wall clock).  The
window opened at ``req.t_admit - flight.t_admit`` on that clock: the
engine stamps ``t_admit``, the harness keeps it relative to the open.
A program without the counter reports nothing."""


def read(rec):
    try:
        from repro.obs.metrics import compile_times
    except ImportError:
        return None
    flights = [f for f in rec["flights"] if f.t_admit is not None]
    if not flights:
        return None
    opened = flights[0].req.t_admit - flights[0].t_admit
    close = max([rec["window_s"]] + [t["t1"] for t in rec["ticks"]
                                     if t["t0"] < rec["window_s"]])
    return sum(opened <= t <= opened + close for t in compile_times())
