#!/usr/bin/env python3
"""Smoke run of the int8 CNN2Gate executor on one TPU chip.

Drives the main path once per model, through the entry points a user
calls, at the paper's published widths (AlexNet and VGG-16 at 224x224,
random weights from ``--seed``):

    parse -> calibrate_quantization -> build("fullflow") -> logits

and checks what comes out:

  * every conv/FC stage is one compiled Mosaic kernel
    (``tpu_custom_call``): nothing interprets, nothing was replaced;
  * the executor's output is bit-exact against the stagewise oracle
    replay of the same quantized program (``kernels/ref.py`` oracles,
    plain XLA ops, same chip, the same dequantizing egress);
  * the dequantized output agrees with the float model
    (``cnn.run_float`` at highest precision): top-1 and max |delta|.

Times printed here are a smoke reading, not a benchmark.  Needs a TPU:
with none, it exits non-zero before doing anything.  The last line of
its output is one JSON object, ``{"ok": true, "device": {...}}``.

    python chip_smoke.py [--seed 0]
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

#: the paper's two networks (repro.models.cnn builders)
MODELS = ("alexnet", "vgg16")
#: images timed one at a time, and the size of the batched call
SINGLE_IMAGES, BATCH = 4, 8

#: Agreement with the float model.  Power-of-two per-tensor int8
#: quantization of random-weight networks moves the softmax
#: probabilities: on a v5e chip, seed 0, 12 images, by up to 0.035
#: (AlexNet) and 0.104 (VGG-16).  The bound leaves room for other seeds
#: and still fails a broken output: mis-scaled logits flatten the
#: softmax and move the top probability by ~0.99.  Top-1 is reported,
#: not gated: within the bound it can flip only where the float model's
#: leader is ahead by less than twice the bound.
MAX_PROB_DELTA = 0.25


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _wall(fn, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def smoke_model(name: str, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import parser as P
    from repro.core import pipeline as pipe
    from repro.core.synthesis import CNN2Gate
    from repro.models import cnn

    t0 = time.perf_counter()
    graph = getattr(cnn, name)(batch=1, seed=seed)
    gate = CNN2Gate.from_graph(graph)
    n_kernels = sum(li.kind in (P.CONV, P.FC) for li in gate.parsed.layers)
    rng = np.random.default_rng(seed)
    shape = tuple(gate.parsed.input_shape[1:])
    x_cal = rng.standard_normal((1,) + shape).astype(np.float32)
    t1 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        gate.calibrate_quantization(x_cal)
    qm = gate.quantized
    print(f"[{name}] {len(qm.layers)} stages, {n_kernels} conv/FC kernels; "
          f"built and parsed in {t1 - t0:.1f} s, calibrated and quantized "
          f"in {time.perf_counter() - t1:.1f} s", flush=True)

    run = gate.build("fullflow")
    mem = gate.compiled.memory_analysis()
    print(f"[{name}] compiled batch 1 in {gate.synthesis_time_s:.2f} s "
          f"(code {mem.generated_code_size_in_bytes} B, "
          f"temp {mem.temp_size_in_bytes} B)", flush=True)
    n_custom = gate.compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')
    check(n_custom == n_kernels,
          f"{name}: {n_custom} tpu_custom_call in the executable, "
          f"expected one per conv/FC stage ({n_kernels})")
    print(f"[{name}] tpu_custom_call: {n_custom} of {n_kernels}", flush=True)

    xs = rng.standard_normal((SINGLE_IMAGES, 1) + shape).astype(np.float32)
    ys, walls = [], []
    for x in xs:
        y, s = _wall(run, jnp.asarray(x))
        ys.append(np.asarray(y))
        walls.append(s)
    print(f"[{name}] smoke reading, not a benchmark: batch 1 first call "
          f"{walls[0] * 1e3:.2f} ms, steady "
          f"{statistics.median(walls[1:]) * 1e3:.2f} ms", flush=True)

    x8 = jnp.asarray(rng.standard_normal((BATCH,) + shape).astype(np.float32))
    y8, first = _wall(run, x8)
    _, steady = _wall(run, x8)
    print(f"[{name}] smoke reading, not a benchmark: batch {BATCH} first "
          f"call {first:.2f} s (compiles), steady {steady * 1e3:.2f} ms",
          flush=True)

    # bit-exact: the timed executor's output against the stagewise
    # oracle replay, which ends in the same dequantizing egress
    got8 = np.asarray(y8)
    oracle = jax.jit(lambda v: pipe.oracle_replay(qm, v))
    want8 = np.asarray(oracle(x8))
    check(got8.dtype == want8.dtype and got8.shape == want8.shape,
          f"{name}: output {got8.dtype}{got8.shape} vs oracle "
          f"{want8.dtype}{want8.shape}")
    n_diff = int(np.sum(got8 != want8))
    check(n_diff == 0, f"{name}: {n_diff} outputs differ from the "
                       "stagewise oracle replay")
    print(f"[{name}] output bit-exact vs stagewise oracle: "
          f"{got8.size} values, 0 differ", flush=True)

    # the float model at highest precision, on all the images run; op
    # by op, as calibration runs it (jitted, its float weights become
    # constants of one large program that compiles for a minute)
    got = np.concatenate(ys + [got8])
    x_all = jnp.asarray(np.concatenate([xs.reshape((-1,) + shape),
                                        np.asarray(x8)]))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(cnn.run_float(graph, x_all))
    check(got.shape == want.shape and bool(np.all(np.isfinite(got))),
          f"{name}: output {got.shape} vs float {want.shape}, "
          "or not finite")
    delta = float(np.max(np.abs(got - want)))
    agree = got.argmax(-1) == want.argmax(-1)
    print(f"[{name}] vs float: top-1 agreement {int(agree.sum())}/"
          f"{agree.size}, max |delta| {delta:.4f}", flush=True)
    check(delta <= MAX_PROB_DELTA,
          f"{name}: max |delta| {delta} vs float over {MAX_PROB_DELTA}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and inputs")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's default device is "
              f"{platform!r}; nothing was run", file=sys.stderr)
        return 2
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"device_kind {device['kind']}, {device['count']} device(s), "
          f"jax {jax.__version__}", flush=True)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache
    cache = Path(enable_compile_cache())
    n_cached = len(list(cache.iterdir())) if cache.is_dir() else 0
    print(f"compile cache: {cache}, {n_cached} entries at start, size "
          f"limit {jax.config.jax_compilation_cache_max_size} B "
          "(-1: none)", flush=True)

    for name in MODELS:
        smoke_model(name, args.seed)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
