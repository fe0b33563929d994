"""The resnet50 configuration (bench/configs/resnet50.json) against the
program's builder, its calibration and its executor: the rows the
cell's metrics read, the published totals, and the plain reference
over the graph at a small input, on the CPU."""
import json
import math

import numpy as np
import pytest

from bench import counts, harness
from bench.reference import dag_cnn
from conftest import ROOT

CFG = json.loads((ROOT / "bench" / "configs" / "resnet50.json").read_text())
#: the small input the program and the reference are compared at
HW = 64


def _small(cfg=CFG):
    return dict(cfg, input_chw=[3, HW, HW])


@pytest.mark.parametrize("batch", [1, 3])
def test_rows_match_the_parsed_graph(batch):
    from repro.core import parser as P
    from repro.models import cnn

    parsed = P.parse(cnn.resnet50(batch=batch, seed=0))
    stages = [li for li in parsed.layers if li.kind in (P.CONV, P.FC)]
    rows = [r for r in counts.layer_counts(CFG, batch) if r["kind"] in counts.WEIGHTED]
    assert [r["kind"] for r in rows] == [li.kind for li in stages]
    assert [r["macs"] for r in rows] == [li.macs for li in stages]
    assert [r["weights"] for r in rows] == [li.weight_count() for li in stages]
    # a folded add's host writes the merged map, which its row counts
    # as the conv's own output: the same shape
    for r, li in zip(rows, stages):
        assert r["bytes"] == (batch * (math.prod(li.in_shape[1:]) + math.prod(li.out_shape[1:]))
                              + li.weight_count() + 4 * li.c_out)


def test_published_totals():
    rows = counts.layer_counts(CFG, 1)
    kinds = [r["kind"] for r in rows]
    assert (kinds.count("conv"), kinds.count("add"), kinds.count("maxpool"),
            kinds.count("gap"), kinds.count("fc")) == (53, 16, 1, 1, 1)
    shapes = counts.layer_shapes(CFG)
    assert sum(s["kind"] == "conv" and s["kernel"] == 1 for s in shapes) == 36
    assert sum(r["weights"] for r in rows) / 1e6 == pytest.approx(25.50, abs=0.005)
    assert counts.ops_per_image(CFG) / 1e9 == pytest.approx(8.18, abs=0.005)
    # v1.5: every strided 3x3 of a block is its c2, never its first 1x1
    assert all(s["stride"] == 1 for s in shapes if s["name"].endswith("c1"))
    assert [s["name"] for s in shapes if s["kind"] == "conv" and s["kernel"] == 3
            and s["stride"] == 2] == ["l2b1c2", "l3b1c2", "l4b1c2"]


def _program(seed, batch=1):
    """The program's CNN2Gate of resnet50 at HW x HW, calibrated as the
    harness calibrates it, and the calibration image."""
    import jax
    from repro.core.synthesis import CNN2Gate
    from repro.models import cnn

    x_cal = harness.calibration_image(_small(), seed)
    gate = CNN2Gate.from_graph(cnn.resnet50(batch=batch, seed=seed, in_hw=HW))
    with jax.default_device(jax.devices("cpu")[0]), jax.default_matmul_precision("highest"):
        specs = gate.calibrate_quantization(x_cal)
    return gate, specs, x_cal


def test_the_exponents_are_the_programs():
    """Every conv's, FC's and add's (m_w, m_x, m_y) as the program's
    calibration sets them, in the program's stage order (a folded add
    right after its host conv): 16 scale groups, chained through up to
    six identity merges in a row."""
    from repro.core import parser as P

    seed = 4_000_000_013
    gate, specs, x_cal = _program(seed)
    program = []
    for li in gate.parsed.layers:
        if li.kind in (P.CONV, P.FC, P.ADD):
            program.append(specs[li.name])
        if li.merge is not None:
            program.append(specs[li.merge.name])
    ref = dag_cnn.build(_small(), seed, x_cal)
    assert len(program) == 53 + 16 + 1
    assert [(s.m_w, s.m_x, s.m_y) for s in program] == \
        [(e["m_w"], e["m_x"], e["m_y"]) for e in ref.exponents.values()]


def test_the_program_agrees_with_the_reference_within_the_limit():
    seed = 2 ** 31 + 50
    gate, _specs, x_cal = _program(seed, batch=2)
    images = np.random.default_rng([seed, 1]).standard_normal((2, 3, HW, HW), dtype=np.float32)
    got = np.asarray(gate.build("fullflow")(images))
    want = dag_cnn.build(_small(), seed, x_cal).probabilities(images)
    assert harness.logprob_gap(got, want) <= CFG["logprob_gap_limit"]
