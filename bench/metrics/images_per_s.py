"""Images answered over the window's seconds, first put to last fetch."""


def read(rec):
    return rec["images"] / rec["window_s"]
