"""setup_s: process start to window start (host clock) - building the
weights and the engine, loading or compiling its programs, warm-up."""


def read(rec):
    return rec["setup_s"]
