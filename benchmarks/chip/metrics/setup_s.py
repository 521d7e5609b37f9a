"""Process start to the first timed submit: weights from the seed,
compression, the engine build and the warm-up of the cell's shapes."""


def read(rec):
    return rec["setup_s"]
