"""queue_wait_p50_ms: median over the window's requests of due time to
admission into a slot (the engine's ``Request.t_admit`` stamp, on the
same host clock).  A request never admitted counts as infinitely late."""
import math

from bench import stats


def read(rec):
    waits = [f.t_admit - f.due if f.t_admit is not None else math.inf
             for f in rec["flights"]]
    if not waits:
        return None
    p = stats.percentile(waits, 50)
    return p * 1e3 if math.isfinite(p) else None
