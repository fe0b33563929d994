"""Pipelined int8 executor — the "host program" of §4.2.

Takes a parsed model + per-layer (N, m) quantization specs, quantizes
weights/biases once, and runs inference by streaming each pipeline stage
through the fused Pallas kernels (conv+ReLU+pool on the conv kernel, FC
on the same matrix unit with pooling configured pass-through — §5).

The executor is an **interpreter over the DAG stage program**
(DESIGN.md §6): the parser's topologically-scheduled stage list is
executed against a tensor environment of named int8 NHWC activations,
with liveness-based release (a tensor is dropped from the environment
after its last consumer runs, so a residual skip holds exactly as long
as its merge needs it).  Residual ``Add`` stages align their operands'
fixed-point positions with per-operand round-half-up shifts before the
int32 add (see :func:`thread_scales`); grouped/depthwise convs dispatch
to the depthwise band kernel or the exact reference path.

It remains **whole-network fused** (DESIGN.md §3): activations stay
NHWC int8 from ingress to egress — one NCHW->NHWC conversion when the
float input is quantized, one back only if the network ends in a
spatial stage — and every layer's weights are pre-staged into the
kernel-native layout once at :func:`build_quantized` time (conv OIHW ->
HWIO; FC rows permuted so flattening an NHWC activation hits the same
features the NCHW-trained weights expect).  :func:`make_executor`
closes the whole stage program over one ``jax.jit``, so steady-state
calls re-enter a single compiled executable instead of re-dispatching
the Python stage loop — the TPU analogue of the paper's host program
enqueueing one fused command queue.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.qgemm import fc_tiles
from . import parser as P
from . import telemetry
from . import verify as V
from .quantize import INT8_MAX, INT8_MIN, QuantSpec, quantize_weights


@dataclasses.dataclass
class QuantizedLayer:
    """One stage with weights staged in the kernel-native layout:
    conv -> HWIO int8, FC -> (K, N) int8 in NHWC-flatten row order.
    Merge stages carry per-operand alignment shifts instead of weights."""

    info: P.LayerInfo
    spec: Optional[QuantSpec]
    w_q: Optional[jnp.ndarray]
    b_q: Optional[jnp.ndarray]
    operand_shifts: Tuple[int, ...] = ()
    # conv stages with a folded residual add: the merge's own spec
    # (requant shift from the common operand position to m_y); the
    # operand_shifts then align (conv intermediate, skip) in that order
    merge_spec: Optional[QuantSpec] = None


@dataclasses.dataclass
class QuantizedModel:
    """int8-ready pipeline (weights quantized with the *given* specs)."""

    name: str
    layers: List[QuantizedLayer]
    input_m: int          # fixed-point exponent of the network input
    output_m: int
    parsed: P.ParsedModel
    _executors: Dict[Tuple, Callable] = dataclasses.field(
        default_factory=dict, repr=False)

    @property
    def hardware_options(self):
        return self.parsed.hardware_options


def thread_scales(model: P.ParsedModel,
                  specs: Dict[str, QuantSpec]) -> Dict[str, int]:
    """Per-tensor fixed-point exponents implied by the per-layer specs —
    a graph pass over the DAG (the linear scan of the old executor only
    worked because every tensor had exactly one consumer).

    Rules: a weighted stage pins its input tensor at ``m_x`` and its
    output at ``m_y``; pools pass the scale through unchanged (both
    directions, so a pool feeding the first conv resolves too); merge
    stages output at their spec's ``m_y``, or at the minimum operand
    position when no spec was given.  A conv with a folded residual add
    pins its *intermediate* tensor (the unfused conv output) at its own
    ``m_y`` and its stage output at the merge spec's ``m_y`` — the same
    two rules the unfused Conv + Add pair would apply.  Iterated to
    fixpoint; raises if the graph input or output never resolves
    (under-specified specs).

    Per-channel specs change nothing here: tensor positions are
    *activation* scales, which stay per-tensor in every mode (a vector
    ``m_w`` only widens the weighted stage's own requant shift), so
    merge-alignment groups keep aligning on scalar positions.
    """
    tensor_m: Dict[str, int] = {}
    for _ in range(len(model.layers) + 2):
        changed = False

        def _set(t: str, m: int) -> None:
            nonlocal changed
            if t not in tensor_m:
                tensor_m[t] = m
                changed = True

        for li in model.layers:
            spec = specs.get(li.name)
            if li.kind in (P.CONV, P.FC):
                if spec is None:
                    raise KeyError(f"no QuantSpec for layer {li.name!r}")
                _set(li.inputs[0], spec.m_x)
                if li.kind == P.CONV and li.merge is not None:
                    _set(li.merge_intermediate, spec.m_y)
                    mspec = specs.get(li.merge.name)
                    if mspec is not None:
                        _set(li.output, mspec.m_y)
                    elif li.skip_input in tensor_m:
                        _set(li.output,
                             min(spec.m_y, tensor_m[li.skip_input]))
                else:
                    _set(li.output, spec.m_y)
            elif li.kind == P.POOL:
                if li.inputs[0] in tensor_m:
                    _set(li.output, tensor_m[li.inputs[0]])
                elif li.output in tensor_m:
                    _set(li.inputs[0], tensor_m[li.output])
            else:  # add / concat
                if spec is not None:
                    _set(li.output, spec.m_y)
                elif all(t in tensor_m for t in li.inputs):
                    _set(li.output, min(tensor_m[t] for t in li.inputs))
        if not changed:
            break
    for t in (model.input_name, model.output_name):
        if t not in tensor_m:
            raise ValueError("could not resolve fixed-point position of "
                             f"tensor {t!r} from the given specs")
    return tensor_m


def _stage_weights(li: P.LayerInfo, prev: Optional[P.LayerInfo],
                   w_q: np.ndarray) -> np.ndarray:
    """One-time layout staging (ingress-side, never per inference):
    conv OIHW -> HWIO; FC weight rows reordered from the exporter's
    NCHW-flatten order (c, h, w) to the executor's NHWC-flatten order
    (h, w, c) when the FC consumes a flattened spatial tensor.  ``prev``
    is the stage *producing* the FC's input tensor (DAG producer, not
    list predecessor)."""
    if li.kind == P.CONV:
        return np.transpose(w_q, (2, 3, 1, 0))
    if li.kind == P.FC and prev is not None and len(prev.out_shape) == 4:
        _n, c, h, w = prev.out_shape
        k, n_out = w_q.shape
        if k == c * h * w:
            return (w_q.reshape(c, h, w, n_out)
                    .transpose(1, 2, 0, 3)
                    .reshape(k, n_out))
    return w_q


def _check_group(li: P.LayerInfo) -> None:
    """Every grouped conv must be executable *as a grouped conv* —
    an invalid group can never fall through to the dense kernel and
    produce silently wrong numerics."""
    g = li.group
    if g < 1 or li.c_in % g or li.c_out % g:
        raise NotImplementedError(
            f"conv {li.name!r}: group={g} does not divide "
            f"C_in={li.c_in}/C_out={li.c_out}; the executor cannot map "
            "this onto the grouped kernel library")


def build_quantized(model: P.ParsedModel,
                    specs: Dict[str, QuantSpec],
                    per_channel: Optional[bool] = None,
                    verify: bool = True) -> QuantizedModel:
    """Apply the user-given (N, m) pairs (the paper: CNN2Gate does not
    *perform* quantization, it *applies* provided values) and stage all
    weights into the kernel-native layouts.  Merge stages (add/concat)
    get per-operand alignment shifts derived from :func:`thread_scales`;
    a spec for them is optional (default: merge at the minimum operand
    position, no output requant).

    ``per_channel`` selects the weight-scale mode:
      * ``None`` (default) — honour each spec as given: specs with a
        tuple ``m_w`` run the per-lane shift-vector epilogue, scalar
        specs run the unchanged per-tensor path;
      * ``True``  — every weighted layer must run per-channel: scalar
        ``m_w`` specs are widened to uniform per-Cout vectors (bit-
        identical numerics, shift-vector datapath);
      * ``False`` — strict per-tensor: a tuple ``m_w`` raises.
    Activations are per-tensor in every mode, so merge alignment and
    fused-skip epilogues are untouched beyond the conv requant.

    ``verify`` (default on) runs the static design-rule checks of
    :mod:`repro.core.verify` over the program — the cheap structural
    rules before staging, the overflow bounds on the staged int8 arrays
    after — and raises :class:`~repro.core.verify.VerificationError`
    (a ``ValueError``) on any error-severity diagnostic.  Verification
    is pure analysis: the staged program and the executor jaxpr are
    byte-identical with it on or off."""
    if per_channel is not None:
        coerced = {}
        for name, spec in specs.items():
            li = next((l for l in model.layers if l.name == name
                       or (l.merge is not None and l.merge.name == name)),
                      None)
            weighted = (li is not None and li.name == name
                        and li.kind in (P.CONV, P.FC))
            if not per_channel and spec.per_channel:
                raise V.VerificationError([V.Diagnostic(
                    "QV206", V.ERROR, stage=name,
                    detail=f"spec for {name!r} is per-channel but "
                           "per_channel=False was requested")])
            if per_channel and weighted and not spec.per_channel:
                coerced[name] = dataclasses.replace(
                    spec, m_w=(spec.m_w,) * li.c_out)
        specs = dict(specs, **coerced)
    if verify:
        # cheap structural rules first — spec shapes, shift ranges,
        # threading conflicts, merge alignment — so an infeasible spec
        # set fails with structured diagnostics before any staging work
        pre = V.check_spec_shapes(model, specs)
        pre += V.check_requant_shifts(model, specs)
        tm_chk, d_thr = V.thread_scales_checked(model, specs)
        pre += d_thr
        pre += V.check_merge_alignment(model, specs, tm_chk)
        V.VerificationReport(pre).raise_if_errors()
    tensor_m = thread_scales(model, specs)
    layers: List[QuantizedLayer] = []
    for li in model.layers:
        # pool stages carry no weights: int8 passes through at the
        # incoming fixed-point scale (no spec, no requant)
        spec = specs.get(li.name) if li.kind in (P.POOL, P.ADD, P.CONCAT)\
            else specs[li.name]
        w = model.graph.initializers[li.weight] if li.weight else None
        b = model.graph.initializers[li.bias] if li.bias else None
        w_q, b_q = (None, None)
        operand_shifts: Tuple[int, ...] = ()
        merge_spec: Optional[QuantSpec] = None
        if li.kind == P.CONV:
            _check_group(li)
        if li.kind == P.CONV and li.merge is not None:
            # folded residual add: same shift-only alignment rules as a
            # standalone merge, operands = (conv intermediate, skip)
            m_ops = (tensor_m[li.merge_intermediate],
                     tensor_m[li.skip_input])
            merge_spec = specs.get(li.merge.name)
            if merge_spec is None:
                m_common = min(m_ops)
                merge_spec = QuantSpec(m_w=0, m_x=m_common, m_y=m_common)
            operand_shifts = tuple(m - merge_spec.m_x for m in m_ops)
            if any(s < 0 for s in operand_shifts):
                raise V.VerificationError([V.Diagnostic(
                    "QV202", V.ERROR, stage=li.name,
                    tensor=li.output,
                    detail=f"fused merge {li.merge.name!r}: operand "
                           "position below the common scale "
                           f"m={merge_spec.m_x} (shifts {operand_shifts})"
                           " — shift-only alignment cannot scale up")])
        if li.kind in (P.ADD, P.CONCAT):
            m_ops = [tensor_m[t] for t in li.inputs]
            if spec is None:
                m_common = min(m_ops)
                spec = QuantSpec(m_w=0, m_x=m_common, m_y=m_common)
            operand_shifts = tuple(m - spec.m_x for m in m_ops)
            if any(s < 0 for s in operand_shifts):
                raise V.VerificationError([V.Diagnostic(
                    "QV202", V.ERROR, stage=li.name, tensor=li.output,
                    detail=f"merge {li.name!r}: operand position below "
                           f"the common scale m={spec.m_x} (shifts "
                           f"{operand_shifts}) — shift-only alignment "
                           "cannot scale up")])
        if w is not None:
            w_q, b_q = quantize_weights(w, b, spec)
            prev_info = model.stage_producing(li.inputs[0])
            w_q = jnp.asarray(_stage_weights(li, prev_info, w_q))
            b_q = jnp.asarray(b_q) if b_q is not None else None
        layers.append(QuantizedLayer(li, spec, w_q, b_q, operand_shifts,
                                     merge_spec))
    if verify:
        # the deep rules run on the staged program: overflow bounds on
        # the actual int8 arrays (no re-quantization), alias/liveness of
        # the schedule, fused/unfused threading identity
        post = V.check_accumulators(model, specs, quantized_layers=layers)
        post += V.check_concat_partition(model)
        post += V.check_liveness(model)
        post += V.check_threading_identity(model, specs)
        V.VerificationReport(post).raise_if_errors()
    return QuantizedModel(
        name=model.name,
        layers=layers,
        input_m=tensor_m[model.input_name],
        output_m=tensor_m[model.output_name],
        parsed=model,
    )


def _concat_axis(axis: int, ndim: int) -> int:
    """Map an NCHW concat axis onto the executor's NHWC layout."""
    if ndim == 4:
        return {0: 0, 1: 3, 2: 1, 3: 2}[axis % 4]
    return axis


def _apply_tensor_faults(h: jnp.ndarray, f: Dict) -> jnp.ndarray:
    """Apply in-flight activation faults (core/faults.py) to one named
    tensor inside the jitted program: XOR bit masks at flat indices
    (SEU bit flips) and zeroed flat ranges (dropped bursts)."""
    flat = h.reshape(-1)
    idx = f.get("xor_idx")
    if idx is not None and len(idx):
        ji = jnp.asarray(idx)
        mask = jnp.asarray(f["xor_mask"]).astype(h.dtype)
        flat = flat.at[ji].set(jax.lax.bitwise_xor(flat[ji], mask))
    z = f.get("zero_idx")
    if z is not None and len(z):
        flat = flat.at[jnp.asarray(z)].set(0)
    return flat.reshape(h.shape)


def _stage_stats(h: jnp.ndarray) -> jnp.ndarray:
    """int8-domain audit statistics of one stage output, computed
    inside the jitted closure: ``[saturation fraction, max |value|,
    mean |value|]``.  The guard (core/guard.py) dequantizes these
    host-side with the tensor's fixed-point position and compares them
    against calibration-time envelopes."""
    sat = jnp.mean(((h == INT8_MAX) | (h == INT8_MIN))
                   .astype(jnp.float32))
    a = jnp.abs(h.astype(jnp.int32)).astype(jnp.float32)
    return jnp.stack([sat, jnp.max(a), jnp.mean(a)])


def _apply_arg_faults(h: jnp.ndarray, entry) -> jnp.ndarray:
    """Apply a *call-time* activation-fault payload ``(idx, mask)`` to
    one tensor: XOR ``mask[k]`` into flat element ``idx[k]``.  Unlike
    the static ``faults=`` payload this one is a closure argument, so a
    whole batch of sampled fault trials can be vmapped through ONE
    compiled program (core/ser.py).  A zero mask is the identity, which
    is how padded/no-op trial slots ride along for free."""
    idx, mask = entry
    flat = h.reshape(-1)
    flat = flat.at[jnp.asarray(idx)].set(
        jax.lax.bitwise_xor(flat[jnp.asarray(idx)],
                            jnp.asarray(mask).astype(h.dtype)))
    return flat.reshape(h.shape)


def _quantize_input(qm: QuantizedModel, x_float: jnp.ndarray) -> jnp.ndarray:
    """Network ingress: float NCHW input -> int8 at the input's
    fixed-point position, NHWC (the single ingress layout change)."""
    scale = 2.0 ** qm.input_m
    h = jnp.clip(jnp.round(x_float * scale), -128, 127).astype(jnp.int8)
    if h.ndim == 4:
        h = jnp.transpose(h, (0, 2, 3, 1))
    return h


def _int8_output(h: jnp.ndarray) -> jnp.ndarray:
    """The network's int8 output in the graph's layout (NHWC -> NCHW
    when it is spatial: the single egress layout change)."""
    return jnp.transpose(h, (0, 3, 1, 2)) if h.ndim == 4 else h


def _dequantize_output(qm: QuantizedModel, h: jnp.ndarray) -> jnp.ndarray:
    """Network egress: int8 output -> float logits at the output's
    fixed-point position (then the output stage's fused softmax)."""
    logits = _int8_output(h).astype(jnp.float32) * (2.0 ** -qm.output_m)
    out_stage = qm.parsed.stage_producing(qm.parsed.output_name)
    if out_stage is not None and out_stage.softmax:
        logits = jax.nn.softmax(logits, axis=-1)
    return logits


def _record_fc_tiles(stage: str, m: int, k: int, n: int) -> None:
    """The FC stage's blocks and grid steps as ``cnn2gate.fc.<stage>.*``
    gauges on the default registry.  Called while the executor traces,
    so once per compile and never per request."""
    t = fc_tiles(m, k, n)
    reg = telemetry.get_registry()
    for key, v in (("block_m", t.bm), ("block_k", t.bk), ("block_n", t.bn),
                   ("grid_steps", t.grid_steps),
                   ("weight_block_bytes", t.weight_block_bytes)):
        reg.gauge(f"cnn2gate.fc.{stage}.{key}").set(v)


def make_executor(qm: QuantizedModel, n_i: int = 16, n_l: int = 32,
                  block_h: Optional[int] = None,
                  interpret: Optional[bool] = None,
                  *,
                  audit=False,
                  faults: Optional[Dict[str, Dict]] = None,
                  checkpoints=None,
                  weight_args=(),
                  fault_args=(),
                  replay_from: Optional[int] = None,
                  stage_timed: bool = False,
                  tracer=None
                  ) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Build the whole-network fused executor: ONE jitted closure that
    interprets the DAG stage program over a tensor environment.
    ``x_float`` is the NCHW float input; the result is float logits
    (dequantized with the output tensor's m).

    (N_i, N_l, block_h) select the conv kernels' tile shapes: N_l lanes
    -> output-channel tile (x8: eight 8-bit MACs per lane-vector element
    feed one MXU row), N_i -> ``block_cin = 8*N_i`` input-channel
    contraction tile (the conv kernel's innermost grid axis — a real
    blocking knob, not just an analytical report), block_h -> the conv
    kernel's row-band height (the line-buffer depth of DESIGN.md §2).
    The FC kernel's tiles follow from each layer's shape
    (``qgemm.fc_tiles``: multi-MiB weight blocks, since an FC layer at
    small batch only streams its weights); tracing records them on the
    default telemetry registry as ``cnn2gate.fc.<stage>.*`` gauges.
    Functionally the result is identical for every option — options
    trade resources for speed, exactly as in the paper.

    Conv stages with a folded residual add (``li.merge``) feed the skip
    operand straight into the kernel epilogue — no standalone add stage
    exists in the jitted program, so the merged feature map never
    round-trips through HBM between conv and add.

    Conv stages annotated for concat fusion (``li.concat``) write their
    output into a channel-offset slice of the merge's shared buffer: the
    buffer is allocated once at the first producer (tracked in the
    environment under a reserved ``"\\x00cbuf:"`` key so it can never
    collide with a graph tensor name), each producer's kernel call
    aliases it in and out with its own ``out_off``/``concat_shift``/
    ``concat_relu`` (and the merge's absorbed pool, when present), and
    the annotated Concat stage itself just *unwraps* the finished buffer
    as the merge tensor — no ``concatenate`` appears anywhere in the
    jitted program.  Liveness is exact: the buffer key is released at
    the Concat stage, which by construction runs after the last
    contributor.

    Buffer release is liveness-based: the stage index of each tensor's
    last consumer is precomputed, and the environment drops a tensor as
    soon as the schedule passes it — the program's peak live set (what
    the FPGA would hold in DDR-visible buffers) is what the DSE's branch
    rules score, not one threaded activation.

    ``audit=True`` makes the closure additionally return per-stage
    int8 audit statistics (``{tensor: [sat_frac, max_abs, mean_abs]}``)
    for the guarded-execution layer; a *collection* of tensor names
    audits only those stages (selective hardening, DESIGN.md §11 —
    the stats cost scales with the audited set).  ``faults`` injects
    in-flight activation faults (see core/faults.py).  All hooks
    default off, and when off NOTHING extra is traced — the emitted
    jaxpr is byte-identical to the unguarded executor (probed in
    tests).

    Resilience hooks (all trace-time-only; DESIGN.md §11):

      * ``checkpoints`` — stage indices at which the closure snapshots
        the live int8 tensor environment (exactly what a replay needs:
        the liveness pass guarantees the snapshot is sufficient and
        minimal).  The closure then also returns ``{stage_name:
        {tensor: int8 array}}``.  Boundaries inside a fused-concat
        group (shared merge buffer under construction) are rejected.
      * ``replay_from`` — build a *replay* closure instead: it takes a
        checkpoint environment (as returned above) and runs only the
        stages AFTER the given boundary index.  Recovery cost is
        bounded by the stages downstream of the boundary, not the
        network depth.
      * ``weight_args`` — stage names whose staged weights become a
        call-time argument (``ex(x, {stage: w_q})``): a batch of
        fault-injected weight images vmaps through one compiled
        program instead of rebuilding an executor per trial.
      * ``fault_args`` — tensor names whose activation-fault payload
        ``(idx, mask)`` becomes a call-time argument
        (``ex(x, ..., {tensor: (idx, mask)})``); a zero mask is a
        no-op slot, so fixed-shape trial batches vmap cleanly.

    ``stage_timed=True`` builds the **stage-timed executor** instead
    (DESIGN.md §12): every DAG stage (plus the ingress quantize and the
    egress dequant) is compiled as its OWN jitted sub-closure over the
    live tensor environment, and the returned callable runs them in
    schedule order with ``jax.block_until_ready`` between stages —
    measured per-stage wall time, the attribution input
    ``launch/profile.py`` joins against the analytical cost models.
    Returns ``(logits, timings)`` where ``timings`` is a schedule-order
    list of ``{"stage", "kind", "wall_us"}`` rows; an optional
    ``tracer`` (:class:`repro.core.telemetry.Tracer`) additionally
    records each stage as a trace span.  Numerics are identical to the
    fused closure (same stage program, same kernels); only the jit
    boundary moves, so per-stage times include each sub-closure's
    dispatch and device sync — honest about what stage-at-a-time
    execution costs, which is exactly the quantity the fused/stagewise
    benchmarks compare.  Exclusive with every other hook, and
    trace-time-only: ``stage_timed=False`` (the default) traces the
    byte-identical whole-network program.

    Return value composition (fixed order): ``logits``, then ``stats``
    when auditing, then ``ckpts`` when checkpointing.
    """
    block_cout = max(8 * n_l, 8)
    block_cin = max(8 * n_i, 8)
    stages = qm.layers
    out_name = qm.parsed.output_name
    in_name = qm.parsed.input_name

    last_use: Dict[str, int] = {}
    for idx, ql in enumerate(stages):
        for t in ql.info.inputs:
            last_use[t] = idx
    last_use[out_name] = len(stages)  # the egress reads it

    # ---- resilience-hook configuration (all static / trace-time) ----
    audit_sel = None if isinstance(audit, bool) else frozenset(audit)
    want_stats = audit is not False
    if stage_timed and (want_stats or faults or checkpoints
                        or weight_args or fault_args
                        or replay_from is not None):
        raise ValueError(
            "stage_timed is exclusive with the audit/faults/checkpoints/"
            "weight_args/fault_args/replay_from hooks: the stage-timed "
            "executor measures the plain program")

    def _audited(t: str) -> bool:
        return audit is True or (audit_sel is not None and t in audit_sel)

    weight_arg_set = frozenset(weight_args or ())
    weighted_names = {ql.info.name for ql in stages if ql.w_q is not None}
    unknown_w = weight_arg_set - weighted_names
    if unknown_w:
        raise ValueError("weight_args name stages without staged "
                         f"weights: {sorted(unknown_w)}")
    fault_arg_set = frozenset(fault_args or ())
    known_tensors = {ql.info.output for ql in stages} | {in_name}
    unknown_f = fault_arg_set - known_tensors
    if unknown_f:
        raise ValueError("fault_args name unknown tensors: "
                         f"{sorted(unknown_f)}")

    ckpt_idx = tuple(sorted({int(c) for c in (checkpoints or ())}))
    if ckpt_idx and replay_from is not None:
        raise ValueError("checkpoints and replay_from are exclusive: a "
                         "replay closure never snapshots")
    # boundary legality (range + never inside a fused-concat group) is
    # the verifier's QV304 rule — one shared implementation with the
    # checkpoint planner, so executor and planner can never disagree
    bad = V.check_checkpoint_boundaries(qm.parsed, ckpt_idx)
    if bad:
        raise V.VerificationError(bad)
    if replay_from is not None and not -1 <= replay_from < len(stages):
        raise ValueError(f"replay_from={replay_from} outside [-1, "
                         f"{len(stages)})")
    ckpt_set = frozenset(ckpt_idx)
    has_w_arg = bool(weight_arg_set)
    has_f_arg = bool(fault_arg_set)

    # concat fusion: producers need their merge's alignment shifts and
    # relu flag, which live on the (still-scheduled) Concat stage
    concat_ql = {ql.info.name: ql for ql in stages
                 if ql.info.kind == P.CONCAT}

    def _cbuf_key(cc: P.LayerInfo) -> str:
        return "\x00cbuf:" + cc.name

    def _extra(extra):
        """Split the optional positional tail into (weights, payload)."""
        i = 0
        weights = None
        payload = None
        if has_w_arg:
            weights = extra[i]
            i += 1
        if has_f_arg:
            payload = extra[i]
            i += 1
        if i != len(extra):
            raise TypeError(f"executor expected {i} extra argument(s) "
                            f"(weights={has_w_arg}, faults={has_f_arg}), "
                            f"got {len(extra)}")
        return weights, payload

    def _pack(logits, stats, ckpts):
        out = (logits,)
        if want_stats:
            out += (stats,)
        if ckpt_set:
            out += (ckpts,)
        return out if len(out) > 1 else logits

    def _exec_stages(env: Dict[str, jnp.ndarray], weights, payload,
                     start: int, stop: int, stats, ckpts) -> None:
        """Interpret stages ``[start, stop)`` over a live tensor
        environment, mutating ``env``/``stats``/``ckpts`` in place —
        the shared core of the forward, replay and stage-timed paths."""

        def _w(ql):
            if weights is not None and ql.info.name in weight_arg_set:
                return weights[ql.info.name]
            return ql.w_q

        for idx in range(start, stop):
            ql = stages[idx]
            li = ql.info
            # the stage's name on every op it emits (trace-time only)
            with jax.named_scope(li.name):
                if li.kind == P.CONV:
                    pool = None
                    if li.pool is not None:
                        pool = (li.pool.kernel_shape[0], li.pool.strides[0])
                    merge_kw = {}
                    if li.merge is not None:  # residual add in the epilogue
                        merge_kw = dict(
                            skip=env[li.skip_input],
                            skip_shifts=ql.operand_shifts,
                            merge_shift=ql.merge_spec.requant_shift,
                            merge_relu=li.merge.relu)
                    if li.concat is not None:  # concat merge in the epilogue
                        cc = li.concat
                        cq = concat_ql[cc.name]
                        if cc.pool is not None:  # pool absorbed by the merge
                            pool = (cc.pool.kernel_shape[0], cc.pool.strides[0])
                        key = _cbuf_key(cc)
                        buf = env.get(key)
                        if buf is None:  # first contributor allocates
                            _nb, c_, h_, w_ = cc.out_shape
                            # batch comes from the traced activation, not
                            # the parse-time shape: the closure must lower
                            # at any batch (fullflow compiles a sample)
                            nb = env[li.inputs[0]].shape[0]
                            buf = jnp.zeros((nb, h_, w_, c_), jnp.int8)
                        merge_kw.update(
                            out_buf=buf,
                            out_off=li.concat_offset,
                            concat_shift=cq.operand_shifts[
                                cc.inputs.index(li.output)],
                            concat_relu=cc.relu)
                    h = ops.qconv2d_nhwc(
                        env[li.inputs[0]], _w(ql), ql.b_q,
                        strides=li.strides, pads=li.pads,
                        shift=ql.spec.requant_shift, relu=li.relu, pool=pool,
                        groups=li.group, block_cout=block_cout, block_h=block_h,
                        block_cin=block_cin, interpret=interpret, **merge_kw)
                    if li.concat is not None:
                        # h IS the shared buffer; the producer's own output
                        # tensor exists only as a channel slice of it.
                        # Faults/audit addressing that tensor act on the
                        # slice (written back via a dynamic update), so the
                        # resilience layer sees fused and standalone
                        # programs the same way.
                        has_static = bool(faults) and li.output in faults
                        has_arg = li.output in fault_arg_set
                        if has_static or has_arg or _audited(li.output):
                            off = li.concat_offset
                            sl = jax.lax.slice_in_dim(h, off, off + li.c_out,
                                                      axis=3)
                            if has_static:
                                sl = _apply_tensor_faults(sl, faults[li.output])
                            if has_arg:
                                sl = _apply_arg_faults(sl, payload[li.output])
                            if has_static or has_arg:
                                h = jax.lax.dynamic_update_slice_in_dim(
                                    h, sl, off, axis=3)
                            if _audited(li.output):
                                stats[li.output] = _stage_stats(sl)
                        env[_cbuf_key(li.concat)] = h
                        for t in li.inputs:  # liveness still applies
                            if last_use.get(t) == idx:
                                env.pop(t, None)
                        continue
                elif li.kind == P.POOL:
                    pool_fn = (ops.avgpool2d_nhwc if li.pool_type == "avg"
                               else ops.maxpool2d_nhwc)
                    h = pool_fn(env[li.inputs[0]], li.kernel_shape[0],
                                li.strides[0], li.pads)
                elif li.kind == P.FC:
                    h = env[li.inputs[0]]
                    if h.ndim > 2:
                        # NHWC flatten: rows were permuted at staging time
                        h = h.reshape(h.shape[0], -1)
                    w = _w(ql)
                    _record_fc_tiles(li.name, h.shape[0], *w.shape)
                    h = ops.qgemm(h, w, ql.b_q,
                                  shift=ql.spec.requant_shift,
                                  relu=li.relu, interpret=interpret)
                elif li.kind == P.ADD:
                    h = ops.qadd_nhwc([env[t] for t in li.inputs],
                                      ql.operand_shifts,
                                      shift=ql.spec.requant_shift,
                                      relu=li.relu)
                elif li.kind == P.CONCAT:
                    if li.concat_fused:
                        # the producers already wrote (aligned + relu'd +
                        # pooled) channel slices in place: the shared buffer
                        # IS the merge tensor — just unwrap and release it
                        h = env.pop(_cbuf_key(li))
                    else:
                        xs = [env[t] for t in li.inputs]
                        h = ops.qconcat_nhwc(
                            xs, ql.operand_shifts,
                            axis=_concat_axis(li.axis, xs[0].ndim),
                            relu=li.relu)
                else:  # pragma: no cover - parser only emits the five kinds
                    raise ValueError(li.kind)
                if faults and li.output in faults:
                    h = _apply_tensor_faults(h, faults[li.output])
                if li.output in fault_arg_set:
                    h = _apply_arg_faults(h, payload[li.output])
                if _audited(li.output):
                    stats[li.output] = _stage_stats(h)
                env[li.output] = h
                for t in li.inputs:     # liveness-based buffer release
                    if last_use.get(t) == idx:
                        env.pop(t, None)  # pop: an operand may repeat (x + x)
                if idx in ckpt_set:
                    # snapshot AFTER the liveness release: the environment
                    # holds exactly the live set — what a replay from this
                    # boundary needs, and nothing more
                    ckpts[li.name] = dict(env)

    def _egress(env: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        with jax.named_scope("egress"):
            return _dequantize_output(qm, env[out_name])

    def _run(env: Dict[str, jnp.ndarray], weights, payload, start: int):
        stats: Dict[str, jnp.ndarray] = {}
        ckpts: Dict[str, Dict[str, jnp.ndarray]] = {}
        _exec_stages(env, weights, payload, start, len(stages),
                     stats, ckpts)
        return _egress(env), stats, ckpts

    def _ingress(x_float: jnp.ndarray, payload) -> jnp.ndarray:
        with jax.named_scope("ingress"):
            h = _quantize_input(qm, x_float)
            if faults and in_name in faults:
                h = _apply_tensor_faults(h, faults[in_name])
            if in_name in fault_arg_set:
                h = _apply_arg_faults(h, payload[in_name])
            return h

    if stage_timed:
        return _make_stage_timed(qm, stages, in_name, _ingress,
                                 _exec_stages, _egress, tracer)

    if replay_from is not None:
        def replay(env: Dict[str, jnp.ndarray], *extra):
            weights, payload = _extra(extra)
            logits, stats, _ = _run(dict(env), weights, payload,
                                    replay_from + 1)
            return _pack(logits, stats, {})
        return jax.jit(replay)

    def forward(x_float: jnp.ndarray, *extra):
        weights, payload = _extra(extra)
        h = _ingress(x_float, payload)
        env: Dict[str, jnp.ndarray] = {in_name: h}
        logits, stats, ckpts = _run(env, weights, payload, 0)
        return _pack(logits, stats, ckpts)

    return jax.jit(forward)


def _make_stage_timed(qm: QuantizedModel, stages, in_name: str,
                      ingress: Callable, exec_stages: Callable,
                      egress: Callable, tracer) -> Callable:
    """Assemble the stage-timed executor (``make_executor(
    stage_timed=True)``): one jitted sub-closure per DAG stage over the
    live tensor environment, run in schedule order with a device sync
    between stages so each stage's wall time is attributable.  Ingress
    (quantize + layout) and egress (dequant + softmax) are timed as
    their own pseudo-stages — they are real work the fused closure also
    pays, and the attribution report should see 100 % of the wall."""

    def _stage_fn(idx: int) -> Callable:
        def f(env: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
            env = dict(env)
            exec_stages(env, None, None, idx, idx + 1, {}, {})
            return env
        return jax.jit(f)

    stage_fns = [_stage_fn(i) for i in range(len(stages))]
    ingress_fn = jax.jit(lambda x: ingress(x, None))
    egress_fn = jax.jit(egress)

    def timed(x_float: jnp.ndarray):
        timings: List[Dict[str, object]] = []

        def _t0():
            return (time.perf_counter(),
                    tracer.now_us() if tracer is not None else 0.0)

        def _rec(name: str, kind: str, t0, ts_us) -> None:
            dur_us = (time.perf_counter() - t0) * 1e6
            timings.append({"stage": name, "kind": kind,
                            "wall_us": dur_us})
            if tracer is not None:
                tracer.add_span(name, ts_us, dur_us, cat="stage",
                                args={"kind": kind,
                                      "model": qm.name})

        t0, ts = _t0()
        h = jax.block_until_ready(ingress_fn(x_float))
        _rec("ingress", "ingress", t0, ts)
        env: Dict[str, jnp.ndarray] = {in_name: h}
        for idx, fn in enumerate(stage_fns):
            li = stages[idx].info
            t0, ts = _t0()
            env = jax.block_until_ready(fn(env))
            _rec(li.name, li.kind, t0, ts)
        t0, ts = _t0()
        logits = jax.block_until_ready(egress_fn(env))
        _rec("egress", "egress", t0, ts)
        return logits, timings

    return timed


def run_int8(qm: QuantizedModel, x_float: jnp.ndarray,
             n_i: int = 16, n_l: int = 32,
             interpret: Optional[bool] = None,
             block_h: Optional[int] = None) -> jnp.ndarray:
    """Full pipelined inference through the fused executor.  Executors
    are cached per (N_i, N_l, block_h, interpret) on the model, so
    repeated calls hit the same compiled program."""
    key = (n_i, n_l, block_h, interpret)
    ex = qm._executors.get(key)
    if ex is None:
        ex = qm._executors[key] = make_executor(
            qm, n_i, n_l, block_h=block_h, interpret=interpret)
    return ex(x_float)


def oracle_replay(qm: QuantizedModel, x_float: jnp.ndarray,
                  dequantize: bool = True) -> jnp.ndarray:
    """Stage-by-stage replay of the quantized program on the
    ``kernels/ref.py`` oracles — plain XLA ops, no Pallas kernel — the
    bit-exact reference for the fused executor on any backend.

    Covers every stage kind: conv (dense, grouped, depthwise) with its
    fused residual add and max-pool, pool, FC, add and concat.  A fused
    merge replays as the unfused program it stands for (conv requant,
    then the add's alignment and requant, then the pool); a fused
    concat's producers leave their own tensors, which the concat stage
    aligns, merges and pools.  Returns what the executor returns
    (float logits), or with ``dequantize=False`` the int8 output tensor
    in the graph's layout.  Wrap it in ``jax.jit`` to run it as one
    program."""
    ref = ops.ref
    env: Dict[str, jnp.ndarray] = {
        qm.parsed.input_name: _quantize_input(qm, x_float)}

    def _maxpool(h, pool_stage):
        if pool_stage is None:
            return h
        return ref.maxpool2d_ref(h, pool_stage.kernel_shape[0],
                                 pool_stage.strides[0])

    for ql in qm.layers:
        li = ql.info
        x = env[li.inputs[0]]
        if li.kind == P.CONV:
            p = li.pads
            x = jnp.pad(x, ((0, 0), (p[0], p[2]), (p[1], p[3]), (0, 0)))
            h = ref.qconv2d_ref(x, ql.w_q, ql.b_q, li.strides,
                                ql.spec.requant_shift, li.relu,
                                groups=li.group)
            if li.merge is not None:
                h = ref.qadd_ref([h, env[li.skip_input]], ql.operand_shifts,
                                 ql.merge_spec.requant_shift, li.merge.relu)
            h = _maxpool(h, li.pool)
        elif li.kind == P.POOL:
            pool_fn = (ops.avgpool2d_nhwc if li.pool_type == "avg"
                       else ops.maxpool2d_nhwc)
            h = pool_fn(x, li.kernel_shape[0], li.strides[0], li.pads)
        elif li.kind == P.FC:
            if x.ndim > 2:
                x = x.reshape(x.shape[0], -1)
            h = ref.qgemm_ref(x, ql.w_q, ql.b_q, ql.spec.requant_shift,
                              li.relu)
        elif li.kind == P.ADD:
            h = ref.qadd_ref([env[t] for t in li.inputs], ql.operand_shifts,
                             ql.spec.requant_shift, li.relu)
        elif li.kind == P.CONCAT:
            xs = [env[t] for t in li.inputs]
            h = ref.qconcat_ref(xs, ql.operand_shifts,
                                axis=_concat_axis(li.axis, xs[0].ndim),
                                relu=li.relu)
            h = _maxpool(h, li.pool)
        else:  # pragma: no cover - parser only emits the five kinds
            raise ValueError(li.kind)
        env[li.output] = h
    out = env[qm.parsed.output_name]
    return _dequantize_output(qm, out) if dequantize else _int8_output(out)


def layer_bytes(li: P.LayerInfo) -> Tuple[int, int, int]:
    """(input, weight, output) int8 bytes of a stage — feeds the FPGA
    latency model and the memory-schedule report.  Merge stages read
    every operand."""
    if li.kind in (P.ADD, P.CONCAT):
        if li.concat_fused:
            # producer-fused concat: the producers wrote their channel
            # slices straight into the shared buffer, so the merge
            # stage itself moves NOTHING (no operand reads, no merged
            # write) — the whole round trip the fusion saves
            return 0, 0, 0
        if li.kind == P.ADD:
            in_b = len(li.inputs) * int(np.prod(li.in_shape))
        else:
            in_b = int(np.prod(li.out_shape))
        return in_b, 0, int(np.prod(li.out_shape))
    in_b = int(np.prod(li.in_shape))
    if li.kind == P.CONV and li.merge is not None:
        # fused residual merge: the skip operand streams in once; the
        # intermediate conv result never touches memory at all
        in_b += int(np.prod(li.conv_out_shape))
    w_b = li.weight_count()
    out_b = int(np.prod(li.out_shape))
    if li.kind == P.CONV and li.concat is not None\
            and li.concat.pool is not None:
        # concat producer with the merge's absorbed pool: the slice it
        # writes is in pooled geometry
        cc = li.concat
        out_b = int(cc.out_shape[0] * li.c_out * np.prod(cc.out_shape[2:]))
    return in_b, w_b, out_b
