"""Process start to the window's first request: graph, calibration,
compile or cache load, inputs and warm-up."""


def read(rec):
    return rec["setup_s"]
