"""Host clock of calibration's float pass: the program's own span
``cnn2gate.calibrate.float_pass`` around ``collect_activations`` in
``CNN2Gate.calibrate_quantization``, read from the program's default
tracer (the newest such span: a run calibrates once).  None where the
program records no such span."""
from repro.core import telemetry

SPAN = "cnn2gate.calibrate.float_pass"


def read(rec):
    durs = [e["dur"] for e in telemetry.get_tracer().events() if e["name"] == SPAN]
    return durs[-1] / 1e6 if durs else None
