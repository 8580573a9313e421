"""ttft_p90_ms: 90th percentile, over every request due in the window,
of due time to the first token visible to the host (host clock).  A
request that failed, or had no token by the end of the drain, counts as
infinitely late; a percentile that lands on one is not reported."""
import math

from bench import stats


def read(rec):
    late = [f.t_tokens[0] - f.due if f.t_tokens and not f.failed
            else math.inf for f in rec["flights"]]
    if not late:
        return None
    p = stats.percentile(late, 90)
    return p * 1e3 if math.isfinite(p) else None
