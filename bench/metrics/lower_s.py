"""Host clock of tracing and lowering the executor: the program's own
span ``cnn2gate.build.lower`` around ``jitted.lower(sample)`` in
``CNN2Gate.build("fullflow")``, read from the program's default tracer
(the newest such span: a run builds once).  The rest of ``compile_s``
is the span ``cnn2gate.build.compile``: the compile, or its load from
the persistent cache.  None where the program records no such span."""
from repro.core import telemetry

SPAN = "cnn2gate.build.lower"


def read(rec):
    durs = [e["dur"] for e in telemetry.get_tracer().events() if e["name"] == SPAN]
    return durs[-1] / 1e6 if durs else None
