"""Process start to the first timed send: imports, device start-up,
weights, engine (executors from the compile cache), warm traffic."""


def read(rec):
    return rec["setup_s"]
