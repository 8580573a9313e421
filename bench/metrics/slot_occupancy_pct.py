"""slot_occupancy_pct: mean tokens emitted per tick over the window as a
share of the engine's slots, from the engine registry's
``serve_tokens_per_tick`` histogram (mixed and decode ticks)."""


def read(rec):
    c = rec["tokens_per_tick"]
    if not c["count"]:
        return None
    return 100.0 * c["sum"] / (c["count"] * rec["engine"]["n_slots"])
