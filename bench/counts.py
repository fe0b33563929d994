"""Operations and bytes of each layer of a configuration: the one place
the benchmark reckons work.

A configuration's ``layers`` form a graph.  Each entry has a ``kind``
and may carry a ``name`` (default ``<kind><index>``, counting from 1)
and a ``from``: the name of one earlier layer, or ``"input"``; by
default the entry before it.  An ``add`` takes a list of two names.  A
chain, such as alexnet or vgg16, gives neither key.  The kinds:

  * ``conv``: ``out``, ``kernel``, ``stride``, ``pad``, ``relu``, and
    an optional fused max-pool ``pool`` = [kernel, stride];
  * ``fc``: ``out``, ``relu``; it flattens its input;
  * ``maxpool``: a standalone max-pool with ``kernel``, ``stride`` and
    ``pad``;
  * ``add``: two inputs of one shape, with an optional ``relu``;
  * ``gap``: a global average pool to C x 1 x 1.

Everything is worked out from these shapes, never from the shapes a
kernel pads or folds to, so a roofline share reads the same work
whatever kernel implements a layer:

  * operations: 2 per multiply-accumulate (0 for ``maxpool``, ``add``
    and ``gap``);
  * bytes: the int8 inputs and the int8 output (after a conv's fused
    max-pool), plus the int8 weights and the int32 bias of a conv or FC.

A residual add that the program folds into a conv runs inside that
conv's kernel, but its bytes stay on the ``add`` row: a conv roofline
share reads such a network a little low, never high.
"""
from __future__ import annotations

import math
from typing import Dict, List

KINDS = ("conv", "fc", "maxpool", "add", "gap")
#: kinds whose rows carry weights and multiply-accumulates
WEIGHTED = ("conv", "fc")


def _out(size: int, k: int, stride: int, pad: int = 0) -> int:
    return (size + 2 * pad - k) // stride + 1


def _inputs(layer: Dict, name: str, prev: str, known: Dict) -> List[str]:
    src = layer.get("from", prev)
    srcs = src if isinstance(src, list) else [src]
    want = 2 if layer["kind"] == "add" else 1
    if len(srcs) != want:
        raise ValueError(f"layer {name!r}: takes {want} input(s), 'from' gives {len(srcs)}")
    for s in srcs:
        if s not in known:
            raise ValueError(f"layer {name!r}: unknown input {s!r}")
    return srcs


def layer_shapes(cfg: Dict) -> List[Dict]:
    """The configuration's layers with their graph and shapes filled in,
    per image: ``name``, ``inputs`` (the names of the layers read);
    ``in_chw`` and ``out_chw`` for ``conv``, ``maxpool``, ``add`` and
    ``gap``; a conv's own output ``conv_hw`` before its fused pool; an
    FC's ``in_features``."""
    known = {"input": tuple(cfg["input_chw"])}
    prev = "input"
    shapes = []
    for i, layer in enumerate(cfg["layers"]):
        kind = layer["kind"]
        name = layer.get("name", f"{kind}{i + 1}")
        if kind not in KINDS:
            raise ValueError(f"layer {name!r}: unknown kind {kind!r}")
        if name in known:
            raise ValueError(f"layer {name!r}: the name is taken")
        srcs = _inputs(layer, name, prev, known)
        shape = known[srcs[0]]
        st = dict(layer, name=name, inputs=srcs)
        if kind == "fc":
            st["in_features"] = math.prod(shape)
            out = (layer["out"],)
        else:
            if len(shape) != 3:
                raise ValueError(f"layer {name!r}: a {kind} layer needs a C x H x W input, "
                                 f"got {list(shape)}")
            c, h, w = shape
            st["in_chw"] = shape
            if kind == "conv":
                k, s, p = layer["kernel"], layer["stride"], layer["pad"]
                h, w = _out(h, k, s, p), _out(w, k, s, p)
                st["conv_hw"] = (h, w)
                if layer.get("pool"):
                    pk, ps = layer["pool"]
                    h, w = _out(h, pk, ps), _out(w, pk, ps)
                c = layer["out"]
            elif kind == "maxpool":
                k, s, p = layer["kernel"], layer["stride"], layer["pad"]
                h, w = _out(h, k, s, p), _out(w, k, s, p)
            elif kind == "add":
                other = known[srcs[1]]
                if other != shape:
                    raise ValueError(f"layer {name!r}: adds {srcs[0]!r} {list(shape)} "
                                     f"to {srcs[1]!r} {list(other)}")
            else:  # gap
                h = w = 1
            out = (c, h, w)
            st["out_chw"] = out
        known[name] = out
        prev = name
        shapes.append(st)
    return shapes


def layer_counts(cfg: Dict, batch: int) -> List[Dict]:
    """Per layer, for one call at ``batch`` images: ``macs``, ``ops`` (2
    per MAC), ``weights`` (count) and ``bytes`` moved."""
    sizes = {"input": math.prod(cfg["input_chw"])}
    rows = []
    for st in layer_shapes(cfg):
        kind = st["kind"]
        act_in = sum(sizes[s] for s in st["inputs"])
        act_out = math.prod(st["out_chw"]) if "out_chw" in st else st["out"]
        sizes[st["name"]] = act_out
        weights = macs = bias = 0
        if kind == "conv":
            cin = st["in_chw"][0]
            ho, wo = st["conv_hw"]
            k = st["kernel"]
            weights = st["out"] * cin * k * k
            macs = batch * st["out"] * ho * wo * k * k * cin
            bias = 4 * st["out"]
        elif kind == "fc":
            weights = st["in_features"] * st["out"]
            macs = batch * weights
            bias = 4 * st["out"]
        rows.append({
            "name": st["name"], "kind": kind, "macs": macs,
            "ops": 2 * macs, "weights": weights,
            "bytes": batch * (act_in + act_out) + weights + bias,
        })
    return rows


def ops_per_image(cfg: Dict) -> int:
    return sum(r["ops"] for r in layer_counts(cfg, 1))


def roofline_s(rows: List[Dict], peak: Dict) -> float:
    """Least time the chip could take for these layer calls: per call
    the larger of its operations over the int8 peak and its bytes over
    the memory bandwidth, summed."""
    return sum(max(r["ops"] / peak["int8_ops_per_s"],
                   r["bytes"] / peak["hbm_bytes_per_s"]) for r in rows)
