"""Operations and bytes of each conv and FC layer: the one place the
benchmark reckons work.

Everything is worked out from the configuration's own layer list (the
graph's shapes), never from the shapes a kernel pads or folds to, so a
roofline share reads the same work whatever kernel implements a layer:

  * operations: 2 per multiply-accumulate;
  * bytes: the int8 input, the int8 weights, the int8 output after the
    fused max-pool, and the int32 bias.
"""
from __future__ import annotations

from typing import Dict, List


def _out(size: int, k: int, stride: int, pad: int = 0) -> int:
    return (size + 2 * pad - k) // stride + 1


def layer_shapes(cfg: Dict) -> List[Dict]:
    """The configuration's layers with their shapes filled in, per image:
    ``in_chw`` (conv) or ``in_features`` (FC), the conv's own output
    ``conv_hw`` and the stage output after the fused pool."""
    c, h, w = cfg["input_chw"]
    flat = None
    shapes = []
    for i, layer in enumerate(cfg["layers"]):
        st = dict(layer, name=f"{layer['kind']}{i + 1}")
        if layer["kind"] == "conv":
            if flat is not None:
                raise ValueError("a conv layer after an FC layer")
            k, s, p = layer["kernel"], layer["stride"], layer["pad"]
            ho, wo = _out(h, k, s, p), _out(w, k, s, p)
            st.update(in_chw=(c, h, w), conv_hw=(ho, wo))
            if layer.get("pool"):
                pk, ps = layer["pool"]
                ho, wo = _out(ho, pk, ps), _out(wo, pk, ps)
            c, h, w = layer["out"], ho, wo
            st["out_chw"] = (c, h, w)
        elif layer["kind"] == "fc":
            fin = flat if flat is not None else c * h * w
            st.update(in_features=fin)
            flat = layer["out"]
        else:
            raise ValueError(f"unknown layer kind {layer['kind']!r}")
        shapes.append(st)
    return shapes


def layer_counts(cfg: Dict, batch: int) -> List[Dict]:
    """Per conv/FC layer, for one call at ``batch`` images: ``macs``,
    ``ops`` (2 per MAC), ``weights`` (count) and ``bytes`` moved."""
    rows = []
    for st in layer_shapes(cfg):
        cout = st["out"]
        if st["kind"] == "conv":
            cin, h, w = st["in_chw"]
            ho, wo = st["conv_hw"]
            k = st["kernel"]
            weights = cout * cin * k * k
            macs = batch * cout * ho * wo * k * k * cin
            c2, h2, w2 = st["out_chw"]
            act_in, act_out = cin * h * w, c2 * h2 * w2
        else:
            weights = st["in_features"] * cout
            macs = batch * weights
            act_in, act_out = st["in_features"], cout
        rows.append({
            "name": st["name"], "kind": st["kind"], "macs": macs,
            "ops": 2 * macs, "weights": weights,
            "bytes": batch * (act_in + act_out) + weights + 4 * cout,
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
