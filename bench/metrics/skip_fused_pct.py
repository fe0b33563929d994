"""Share of the residual adds the program folded into a conv kernel's
epilogue: the gauges ``cnn2gate.graph.adds_fused`` and
``cnn2gate.graph.adds`` that ``pipeline.make_executor`` sets on the
program's default registry while the executor traces.  None where the
program sets no such gauges or the graph has no add."""
from repro.core import telemetry

ADDS, FUSED = "cnn2gate.graph.adds", "cnn2gate.graph.adds_fused"


def read(rec):
    gauges = telemetry.get_registry().snapshot()["gauges"]
    if not gauges.get(ADDS) or FUSED not in gauges:
        return None
    return 100.0 * gauges[FUSED] / gauges[ADDS]
