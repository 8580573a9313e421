"""itl_p99_ms: 99th percentile of the gaps between consecutive tokens
of a request, both visible to the host inside the window (host clock,
polled after every engine step)."""
from bench import stats


def read(rec):
    end = rec["window_s"]
    gaps = [b - a for f in rec["flights"]
            for a, b in zip(f.t_tokens, f.t_tokens[1:]) if b <= end]
    return stats.percentile(gaps, 99) * 1e3 if gaps else None
