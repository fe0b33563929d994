"""bench/counts.py against the program's own layer arithmetic and the
published sizes of the two networks."""
import json
import math

import pytest

from bench import counts
from conftest import ROOT


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name,weights_m,gop", [("alexnet", 61.1, 1.43), ("vgg16", 138.3, 30.9)])
@pytest.mark.parametrize("batch", [1, 3])
def test_counts_match_the_parsed_graph(name, weights_m, gop, batch):
    from repro.core import parser as P
    from repro.models import cnn

    cfg = config(name)
    parsed = P.parse(getattr(cnn, cfg["builder"])(batch=batch, seed=0))
    stages = [li for li in parsed.layers if li.kind in (P.CONV, P.FC)]
    rows = counts.layer_counts(cfg, batch)
    assert [r["kind"] for r in rows] == [li.kind for li in stages]
    assert [r["macs"] for r in rows] == [li.macs for li in stages]
    assert [r["weights"] for r in rows] == [li.weight_count() for li in stages]
    for r, li in zip(rows, stages):
        # int8 in, weights and out after the fused pool, int32 bias
        assert r["bytes"] == (batch * (math.prod(li.in_shape[1:]) + math.prod(li.out_shape[1:]))
                              + li.weight_count() + 4 * li.c_out)
    assert sum(r["weights"] for r in rows) / 1e6 == pytest.approx(weights_m, abs=0.05)
    assert counts.ops_per_image(cfg) / 1e9 == pytest.approx(gop, rel=0.01)


def test_roofline_takes_the_larger_bound_per_call():
    peak = {"int8_ops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    rows = [{"ops": 1000, "bytes": 50}, {"ops": 100, "bytes": 100}]
    assert counts.roofline_s(rows, peak) == pytest.approx(10.0 + 10.0)


def test_shapes_follow_stride_pad_and_pool():
    st = counts.layer_shapes(config("alexnet"))
    assert st[0]["conv_hw"] == (55, 55) and st[0]["out_chw"] == (64, 27, 27)
    assert st[4]["out_chw"] == (256, 6, 6) and st[5]["in_features"] == 9216
