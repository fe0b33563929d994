"""bench/counts.py against the program's own layer arithmetic and the
published sizes of the networks, chains and layer graphs alike."""
import json
import math

import pytest

from bench import counts
from conftest import ROOT


def config(name):
    path = ROOT / "bench" / "configs" / f"{name}.json"
    if not path.exists():
        path = ROOT / "bench" / "tests" / f"{name}.json"
    return json.loads(path.read_text())


@pytest.mark.parametrize("name,weights_m,gop", [("alexnet", 61.1, 1.43), ("vgg16", 138.3, 30.9),
                                                ("resnet18", 11.68, 3.63)])
@pytest.mark.parametrize("batch", [1, 3])
def test_counts_match_the_parsed_graph(name, weights_m, gop, batch):
    from repro.core import parser as P
    from repro.models import cnn

    cfg = config(name)
    parsed = P.parse(getattr(cnn, cfg["builder"])(batch=batch, seed=0))
    stages = [li for li in parsed.layers if li.kind in (P.CONV, P.FC)]
    rows = [r for r in counts.layer_counts(cfg, batch) if r["kind"] in counts.WEIGHTED]
    assert [r["kind"] for r in rows] == [li.kind for li in stages]
    assert [r["macs"] for r in rows] == [li.macs for li in stages]
    assert [r["weights"] for r in rows] == [li.weight_count() for li in stages]
    for r, li in zip(rows, stages):
        # int8 in, weights and out after the fused pool, int32 bias
        assert r["bytes"] == (batch * (math.prod(li.in_shape[1:]) + math.prod(li.out_shape[1:]))
                              + li.weight_count() + 4 * li.c_out)
    assert sum(r["weights"] for r in rows) / 1e6 == pytest.approx(weights_m, abs=0.05)
    assert counts.ops_per_image(cfg) / 1e9 == pytest.approx(gop, rel=0.01)


@pytest.mark.parametrize("cell", ["alexnet.b1", "alexnet.b32", "vgg16.b1", "vgg16.b32"])
def test_chain_rows_are_pinned(cell):
    # the rows every chain cell's roofline and mfu metrics read, as they
    # stood before configurations could be graphs
    pinned = json.loads((ROOT / "bench" / "tests" / "chain_counts.json").read_text())
    name, batch = cell.split(".b")
    rows = counts.layer_counts(config(name), int(batch))
    assert [[r[c] for c in pinned["columns"]] for r in rows] == pinned[cell]


def test_graph_layers_take_their_inputs_by_name():
    cfg = config("resnet18")
    st = {s["name"]: s for s in counts.layer_shapes(cfg)}
    assert st["stem"]["inputs"] == ["input"] and st["stem"]["out_chw"] == (64, 112, 112)
    assert st["pool"]["out_chw"] == (64, 56, 56)
    assert st["l1b1add"]["inputs"] == ["l1b1c2", "pool"]
    assert st["l2b1proj"]["inputs"] == ["l1b2add"] and st["l2b1proj"]["out_chw"] == (128, 28, 28)
    assert st["gap"]["out_chw"] == (512, 1, 1) and st["fc"]["in_features"] == 512
    rows = {r["name"]: r for r in counts.layer_counts(cfg, 3)}
    # a layer without weights moves its int8 inputs and output, nothing more
    assert rows["l1b1add"] == {"name": "l1b1add", "kind": "add", "macs": 0, "ops": 0,
                               "weights": 0, "bytes": 3 * 3 * 64 * 56 * 56}
    assert rows["pool"]["bytes"] == 3 * 64 * (112 * 112 + 56 * 56)
    assert rows["gap"]["bytes"] == 3 * 512 * (7 * 7 + 1)
    assert sum(r["kind"] == "add" for r in rows.values()) == 8


def test_default_names_and_inputs_follow_the_chain():
    st = counts.layer_shapes(config("alexnet"))
    assert [s["name"] for s in st] == ["conv1", "conv2", "conv3", "conv4", "conv5",
                                       "fc6", "fc7", "fc8"]
    assert [s["inputs"] for s in st[:3]] == [["input"], ["conv1"], ["conv2"]]


_TINY = {"input_chw": [3, 8, 8], "layers": [
    {"name": "a", "kind": "conv", "out": 4, "kernel": 3, "stride": 1, "pad": 1, "relu": True},
    {"name": "b", "kind": "conv", "out": 4, "kernel": 3, "stride": 2, "pad": 1, "relu": False},
    {"name": "f", "kind": "fc", "out": 2, "relu": False}]}


@pytest.mark.parametrize("extra,match", [
    ({"name": "c", "kind": "avgpool"}, "layer 'c': unknown kind 'avgpool'"),
    ({"name": "c", "kind": "gap", "from": "z"}, "layer 'c': unknown input 'z'"),
    ({"name": "c", "kind": "add", "from": ["a", "b"]}, r"layer 'c': adds 'a' \[4, 8, 8\] to 'b'"),
    ({"name": "c", "kind": "add", "from": "a"}, r"layer 'c': takes 2 input\(s\), 'from' gives 1"),
    ({"name": "a", "kind": "gap"}, "layer 'a': the name is taken"),
    ({"name": "c", "kind": "conv", "from": "f", "out": 4, "kernel": 1, "stride": 1, "pad": 0},
     "layer 'c': a conv layer needs a C x H x W input"),
])
def test_a_malformed_graph_is_refused_naming_the_layer(extra, match):
    cfg = dict(_TINY, layers=_TINY["layers"] + [extra])
    with pytest.raises(ValueError, match=match):
        counts.layer_shapes(cfg)


def test_roofline_takes_the_larger_bound_per_call():
    peak = {"int8_ops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    rows = [{"ops": 1000, "bytes": 50}, {"ops": 100, "bytes": 100}]
    assert counts.roofline_s(rows, peak) == pytest.approx(10.0 + 10.0)


def test_shapes_follow_stride_pad_and_pool():
    st = counts.layer_shapes(config("alexnet"))
    assert st[0]["conv_hw"] == (55, 55) and st[0]["out_chw"] == (64, 27, 27)
    assert st[4]["out_chw"] == (256, 6, 6) and st[5]["in_features"] == 9216
