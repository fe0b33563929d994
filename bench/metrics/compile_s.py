"""Host clock around ``CNN2Gate.build("fullflow")``: the executor's
build and its AOT compile or load from the persistent cache."""


def read(rec):
    return rec["spans"].get("compile")
