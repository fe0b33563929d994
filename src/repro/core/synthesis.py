"""CNN2Gate automated high-level synthesis workflow (§4.2, Fig. 4a).

``CNN2Gate`` is the user-facing orchestrator:

    gate = CNN2Gate.from_graph(alexnet())          # ONNX-lite front end
    gate.apply_quantization(specs)                  # given (N, m) pairs
    fit  = gate.explore("ARRIA10", algo="rl")       # hardware-aware DSE
    run  = gate.build(mode="emulation")             # fast CPU verify
    y    = run(x)                                   # inference
    rep  = gate.latency_report("ARRIA10", *fit.best)  # Table-1 model

Modes (kernels run in Pallas interpret mode only off a TPU, as
``ops.default_interpret()`` decides):
  * ``emulation``  — the jitted executor, compiled at its first call;
    off a TPU this is functional verification exactly like the paper's
    OpenCL emulator (the paper stresses this loop: verify before the
    10-hour synthesis).
  * ``fullflow``   — AOT ``jit(...).lower().compile()`` of the pipeline:
    the TPU-target "synthesis".  On a TPU machine this produces the real
    executable; elsewhere it produces the compiled CPU artifact and the
    resource report (our stand-in for the bitstream + fitter report).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.cnn import collect_activations
from . import dse as dse_mod
from . import parser as P
from . import pipeline as pipe
from . import telemetry as tele
from .graph import Graph
from .quantize import (MAX_SHIFT, QuantSpec, best_pow2_exponent,
                       best_pow2_exponents_per_channel)
from .resources import (FPGA_BOARDS, fpga_layer_time_s)
from .spaces import CNNDesignSpace


def _softmax_input(graph: Graph, tensor: str) -> str:
    """The tensor read by the Softmax node that ``tensor`` comes from
    (through any pass-through nodes the parser fused after it)."""
    node = graph.producer_of(tensor)
    while node.op_type != "Softmax":
        node = graph.producer_of(node.inputs[0])
    return node.inputs[0]


@dataclasses.dataclass
class LayerTiming:
    name: str
    kind: str
    time_s: float
    t_compute: float
    t_memory: float
    macs: int


@dataclasses.dataclass
class LatencyReport:
    board: str
    n_i: int
    n_l: int
    layers: List[LayerTiming]

    @property
    def total_s(self) -> float:
        return sum(l.time_s for l in self.layers)

    @property
    def gops(self) -> float:
        total_ops = 2 * sum(l.macs for l in self.layers)
        return total_ops / self.total_s / 1e9


class CNN2Gate:
    """Parse -> (apply quantization) -> explore -> build -> run."""

    def __init__(self, parsed: P.ParsedModel):
        self.parsed = parsed
        self.quantized: Optional[pipe.QuantizedModel] = None
        self.specs: Optional[Dict[str, QuantSpec]] = None

    # ---------------------------------------------------------- front end
    @classmethod
    def from_graph(cls, graph: Graph, fuse_skip: bool = True,
                   fuse_concat: bool = True) -> "CNN2Gate":
        """``fuse_skip=False`` keeps residual adds as standalone merge
        stages and ``fuse_concat=False`` keeps channel concats as
        standalone copies — the bit-exact fallback/benchmark baseline
        programs."""
        return cls(P.parse(graph, fuse_skip=fuse_skip,
                           fuse_concat=fuse_concat))

    @classmethod
    def from_file(cls, path: str) -> "CNN2Gate":
        from . import onnx_lite
        return cls.from_graph(onnx_lite.load(path))

    # ------------------------------------------------------- quantization
    def apply_quantization(self, specs: Dict[str, QuantSpec],
                           per_channel: Optional[bool] = None) -> None:
        """Apply *given* per-layer (N, m) pairs (§4.2 Physical domain).
        ``per_channel`` is forwarded to :func:`pipeline.build_quantized`
        (None: honour the specs as given)."""
        self.specs = specs
        self.quantized = pipe.build_quantized(self.parsed, specs,
                                              per_channel=per_channel)

    def calibrate_quantization(self, sample_input: np.ndarray,
                               per_channel: bool = False
                               ) -> Dict[str, QuantSpec]:
        """Convenience PTQ (stand-in for the user's external tool) — a
        graph pass over the DAG stage program, not a linear scan.

        Three passes (DESIGN.md §6):

        1. *stats* — max-abs power-of-two exponent for every named
           tensor in the stage program (from the float activations);
        2. *branch-aware alignment* — the operands of every int8
           ``Add``/``Concat`` must agree on fixed-point position
           (shift-only arithmetic cannot scale up), so merge operands
           form a scale group pinned at the group minimum; iterated to
           fixpoint because groups chain through stacked residuals;
        3. *forward threading* — walk the schedule: each weighted
           stage's ``m_x`` is its input tensor's position, ``m_y`` is
           capped at ``m_w + m_x`` (non-negative requant shift); pools
           pass scale through; merges emit a ``QuantSpec(0, m_common,
           m_y)`` whose requant shift is the post-add renormalisation.

        When a producer's ``m_y`` cap lands below its merge group's
        position, the executor's per-operand alignment shifts absorb
        the residual mismatch — alignment is an optimisation (it makes
        those shifts zero), not a correctness requirement.

        ``per_channel=True`` computes **per-output-channel** weight
        exponents (``m_w`` becomes a length-Cout tuple, the max-abs
        rule applied per Cout slice — DESIGN.md §8): each lane
        quantizes at its own power of two and the band epilogues apply
        a per-lane shift vector.  Activations (``m_x``/``m_y``) stay
        per-tensor, so every merge/alignment rule below is unchanged;
        the ``m_y <= m_w + m_x`` non-negative-shift cap simply uses
        the *minimum* lane exponent (every lane's shift must stay
        representable).  Per-tensor calibration is the default.

        On the default tracer the call is the span ``cnn2gate.calibrate``,
        with the float pass (``cnn2gate.calibrate.float_pass``) and the
        quantization of the weights (``cnn2gate.calibrate.quantize``) as
        its children.
        """
        tracer = tele.get_tracer()
        with tracer.span("cnn2gate.calibrate", cat="cnn2gate"):
            return self._calibrate(tracer, sample_input, per_channel)

    def _calibrate(self, tracer: tele.Tracer, sample_input: np.ndarray,
                   per_channel: bool) -> Dict[str, QuantSpec]:
        pm = self.parsed
        with tracer.span("cnn2gate.calibrate.float_pass", cat="cnn2gate"):
            acts = collect_activations(pm.graph, sample_input)
        acts[pm.input_name] = np.asarray(sample_input)
        weights = pm.graph.initializers

        # pass 1: per-tensor desired positions from activation stats
        # (conv stages with a folded residual add still thread their
        # intermediate tensor — it lives on in li.merge.inputs)
        desired: Dict[str, int] = {}
        for li in pm.layers:
            if li.softmax:
                # the int8 stage output holds the logits the fused
                # softmax reads (the egress applies it): scale from the
                # logits, not from the probabilities in [0, 1]
                desired[li.output] = best_pow2_exponent(
                    acts[_softmax_input(pm.graph, li.output)])
            tensors = list(li.inputs) + [li.output]
            if li.merge is not None:
                tensors += list(li.merge.inputs) + [li.merge.output]
            for t in tensors:
                if t not in desired:
                    desired[t] = best_pow2_exponent(acts[t])
        desired.setdefault(pm.input_name,
                           best_pow2_exponent(acts[pm.input_name]))

        # pass 2: merge-operand scale groups -> group minimum (fixpoint)
        changed = True
        while changed:
            changed = False
            for li in pm.layers:
                if li.kind in (P.ADD, P.CONCAT):
                    operands = li.inputs
                elif li.merge is not None:
                    operands = li.merge.inputs
                else:
                    continue
                m = min(desired[t] for t in operands)
                for t in operands:
                    if desired[t] != m:
                        desired[t] = m
                        changed = True

        # pass 3: forward threading over the schedule
        tensor_m: Dict[str, int] = {pm.input_name: desired[pm.input_name]}
        specs: Dict[str, QuantSpec] = {}
        for li in pm.layers:
            if li.kind in (P.CONV, P.FC):
                if per_channel:
                    m_w = best_pow2_exponents_per_channel(weights[li.weight])
                    m_w_cap = min(m_w)  # every lane's shift must be >= 0
                else:
                    m_w = m_w_cap = best_pow2_exponent(weights[li.weight])
                m_x = tensor_m[li.inputs[0]]

                def lane_clamp(m_w, m_y):
                    # keep every lane's shift m_w[c]+m_x-m_y inside the
                    # int32 round-half-up datapath; lanes at the clamp
                    # lose nothing (their shifted-away bits are already
                    # below one output LSB)
                    if not per_channel:
                        return m_w
                    return tuple(min(mw, MAX_SHIFT + m_y - m_x)
                                 for mw in m_w)

                if li.merge is not None:
                    # the conv's own spec scales its intermediate tensor;
                    # the folded merge gets the same spec a standalone
                    # Add stage would have received
                    m_int = min(desired[li.merge_intermediate],
                                m_w_cap + m_x)
                    specs[li.name] = QuantSpec(
                        m_w=lane_clamp(m_w, m_int), m_x=m_x, m_y=m_int)
                    m_common = min(m_int, tensor_m[li.skip_input])
                    # scale from the *merge* output stats (an absorbed
                    # max-pool passes scale through, as when standalone)
                    m_y = min(desired[li.merge.output], m_common)
                    specs[li.merge.name] = QuantSpec(
                        m_w=0, m_x=m_common, m_y=m_y)
                else:
                    m_y = min(desired[li.output], m_w_cap + m_x)
                    specs[li.name] = QuantSpec(
                        m_w=lane_clamp(m_w, m_y), m_x=m_x, m_y=m_y)
                tensor_m[li.output] = m_y
            elif li.kind == P.POOL:
                tensor_m[li.output] = tensor_m[li.inputs[0]]
            else:  # add / concat
                m_common = min(tensor_m[t] for t in li.inputs)
                if li.kind == P.ADD:
                    m_y = min(desired[li.output], m_common)
                else:  # concat never rescales its operands' values
                    m_y = m_common
                specs[li.name] = QuantSpec(m_w=0, m_x=m_common, m_y=m_y)
                tensor_m[li.output] = m_y
        with tracer.span("cnn2gate.calibrate.quantize", cat="cnn2gate"):
            self.apply_quantization(specs)
        return specs

    # ---------------------------------------------------------------- DSE
    @property
    def per_channel(self) -> bool:
        """True when the *built* program runs any per-channel weight
        spec — the DSE then charges the shift-vector bytes.  Reads the
        quantized layers, not the raw specs: apply_quantization(...,
        per_channel=True) widens scalar specs inside build_quantized,
        so the specs dict alone under-reports the datapath."""
        if self.quantized is not None:
            return any(ql.spec is not None and ql.spec.per_channel
                       for ql in self.quantized.layers)
        return bool(self.specs) and any(
            s.per_channel for s in self.specs.values())

    def verify(self, **kw):
        """Run the static design-rule checks (:mod:`repro.core.verify`)
        over the current program and return the
        :class:`~repro.core.verify.VerificationReport`.  With a built
        program the staged int8 arrays feed the overflow bounds; with
        only specs applied the verifier re-quantizes from the graph
        initializers.  Keyword args forward to ``verify_program``
        (``vmem_budget=``, ``checkpoints=``, ...)."""
        from . import verify as verify_mod
        if self.quantized is not None:
            return verify_mod.verify_quantized(self.quantized, **kw)
        if self.specs is None:
            raise RuntimeError("apply_quantization() or "
                               "calibrate_quantization() first")
        return verify_mod.verify_program(self.parsed, self.specs, **kw)

    def design_space(self, board: str,
                     block_h_options: Optional[List[int]] = None
                     ) -> CNNDesignSpace:
        return CNNDesignSpace(self.parsed, FPGA_BOARDS[board],
                              block_h_options=block_h_options,
                              per_channel=self.per_channel,
                              specs=self.specs)

    def explore(self, board: str, algo: str = "rl",
                thresholds: Optional[Dict[str, float]] = None,
                eval_cost_s: float = 0.0,
                block_h_options: Optional[List[int]] = None,
                **kw) -> dse_mod.DSEResult:
        """Hardware-aware DSE.  With ``block_h_options`` the space grows
        a third axis — the conv kernel's row-band height — and options
        whose row-band working set exceeds the on-chip budget are
        rejected by the resource model (DESIGN.md §4)."""
        space = self.design_space(board, block_h_options=block_h_options)
        if algo == "bf":
            return dse_mod.brute_force(space, thresholds, eval_cost_s)
        if algo == "rl":
            return dse_mod.rl_dse(space, thresholds,
                                  eval_cost_s=eval_cost_s, **kw)
        raise ValueError(f"unknown DSE algorithm {algo!r}")

    # -------------------------------------------------------------- build
    def build(self, mode: str = "emulation", n_i: int = 16, n_l: int = 32,
              block_h: Optional[int] = None
              ) -> Callable[[jnp.ndarray], jnp.ndarray]:
        """Return the whole-network fused executor: ONE jitted closure
        over the staged layer list (no per-call Python layer dispatch).
        Kernels interpret only where the backend is not a TPU
        (``ops.default_interpret()``).

        emulation: the jitted executor, compiled at its first call.
        fullflow : AOT-compiled at the graph's input shape before it
        returns (the TPU-target synthesis path; identical numerics);
        ``synthesis_time_s`` is that lowering and compile.  jit's
        executable cache holds the result, so a call at that shape
        compiles nothing more; another batch compiles at its first call.
        On the default tracer a fullflow build is the span
        ``cnn2gate.build``, with ``cnn2gate.build.lower`` (tracing and
        lowering the executor) and ``cnn2gate.build.compile`` (the
        compile, or its load from the persistent cache) as children.
        """
        if self.quantized is None:
            raise RuntimeError("apply_quantization() or "
                               "calibrate_quantization() first")
        qm = self.quantized
        if mode == "emulation":
            return pipe.make_executor(qm, n_i, n_l, block_h=block_h)
        if mode == "fullflow":
            tracer = tele.get_tracer()
            with tracer.span("cnn2gate.build", cat="cnn2gate"):
                jitted = pipe.make_executor(qm, n_i, n_l, block_h=block_h)
                sample = jax.ShapeDtypeStruct(
                    tuple(self.parsed.input_shape), jnp.float32)
                # the "synthesis"
                with tracer.span("cnn2gate.build.lower",
                                 cat="cnn2gate") as lower:
                    lowered = jitted.lower(sample)
                with tracer.span("cnn2gate.build.compile",
                                 cat="cnn2gate") as comp:
                    compiled = lowered.compile()
            self.synthesis_time_s = (lower.dur_us + comp.dur_us) / 1e6
            self.compiled = compiled
            return jitted
        raise ValueError(f"unknown mode {mode!r}")

    def build_guarded(self, x_cal=None, policy=None,
                      qm: Optional[pipe.QuantizedModel] = None,
                      faults: Optional[Dict] = None,
                      mode: str = "emulation", n_i: int = 16,
                      n_l: int = 32, block_h: Optional[int] = None,
                      checkpoints=None):
        """Guarded-execution build (DESIGN.md §9).

        With ``policy=None`` guards are OFF and this returns the plain
        :func:`pipeline.make_executor` closure — the byte-identical
        program (jaxpr-identity probed in tests), zero overhead.

        With a :class:`~repro.core.guard.GuardPolicy`, returns a
        :class:`~repro.core.guard.GuardedExecutor` whose calls yield
        ``(logits, GuardReport)``: per-stage dequant audits against
        envelopes calibrated on ``x_cal`` from the *golden* program,
        plus the reexecute → unfused → per-tensor degradation ladder.
        ``qm``/``faults`` deploy a fault-injected program under the
        guard (defaults: the golden program, no faults);
        ``checkpoints`` (an int K or explicit boundary indices) arms
        the stage-boundary recovery rung (DESIGN.md §11).  Whatever the
        ``mode``, kernels interpret only off a TPU."""
        if self.quantized is None:
            raise RuntimeError("apply_quantization() or "
                               "calibrate_quantization() first")
        if policy is None:
            return pipe.make_executor(qm or self.quantized, n_i, n_l,
                                      block_h=block_h)
        if x_cal is None:
            raise ValueError("guarded mode needs a calibration input "
                             "(x_cal) to record audit envelopes")
        from . import guard as guard_mod
        return guard_mod.GuardedExecutor(
            self, x_cal, policy=policy, qm=qm, faults=faults,
            n_i=n_i, n_l=n_l, block_h=block_h, checkpoints=checkpoints)

    # ------------------------------------------------------ latency model
    def latency_report(self, board: str, n_i: int, n_l: int) -> LatencyReport:
        """Analytical Table-1/Fig-6 latency model (see resources.py).
        Walks the DAG schedule: merge stages are pure memory traffic
        (both operands stream once, zero MACs), so residual networks
        report the adder path the FPGA would pay."""
        profile = FPGA_BOARDS[board]
        rows: List[LayerTiming] = []
        for li in self.parsed.layers:
            in_b, w_b, out_b = pipe.layer_bytes(li)
            t, tc, tm = fpga_layer_time_s(profile, n_i, n_l, li.macs,
                                          in_b, w_b, out_b)
            rows.append(LayerTiming(li.name, li.kind, t, tc, tm, li.macs))
        return LatencyReport(board=board, n_i=n_i, n_l=n_l, layers=rows)

    # ------------------------------------------------------------ summary
    def summary(self) -> str:
        pm = self.parsed
        lines = [f"model {pm.name}: {len(pm.layers)} pipeline stages, "
                 f"{pm.total_ops / 1e9:.2f} GOp, "
                 f"{pm.total_weights / 1e6:.1f} M weights"]
        for li in pm.layers:
            kind = li.kind
            if li.is_depthwise:
                kind = "dwconv"
            elif li.kind == P.CONV and li.group > 1:
                kind = f"gconv[{li.group}]"
            fused = "+relu" if li.relu else ""
            fused += "+pool" if li.pool is not None else ""
            fused += "+softmax" if li.softmax else ""
            ins = (f" <- {len(li.inputs)} tensors"
                   if len(li.inputs) > 1 else "")
            lines.append(f"  {li.name:<12} {kind}{fused:<14} "
                         f"in={li.in_shape} out={li.out_shape} "
                         f"macs={li.macs / 1e6:.1f}M{ins}")
        return "\n".join(lines)
