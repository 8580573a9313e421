"""prefix_hit_pct: prompt tokens that the fleet's prefix cache supplied
to the requests admitted in the window, over all their prompt tokens
(each adoption's count as ``PrefixCache.adopt`` returns it).  Silent
where no prefix cache serves."""


def read(rec):
    if not rec["prefix_cache"]:
        return None
    admitted = [f for f in rec["flights"]
                if f.t_admit is not None and f.t_admit <= rec["window_s"]]
    total = sum(len(f.prompt) for f in admitted)
    if not total:
        return None
    return 100.0 * sum(f.adopted for f in admitted) / total
