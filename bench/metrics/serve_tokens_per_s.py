"""serve_tokens_per_s: output tokens that became visible to the host
inside the window, over the window's length (host clock)."""


def read(rec):
    end = rec["window_s"]
    n = sum(1 for f in rec["flights"] for t in f.t_tokens if t <= end)
    return n / end
