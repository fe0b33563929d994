"""The output check fails what it must: the control (the reference one
precision below the configuration, int4 for int8, in the program's
place) and the program with its timed path broken underneath.  At
tiny_cnn's size on the CPU; the same control at the cells' own sizes is
run on the chip by bench/control.py."""
import time

import jax.numpy as jnp
import pytest

from bench import harness
from test_bench_rehearsal import cpu_peaks, tiny_cell


def _run(make, seed=2 ** 31 + 23):
    return harness.run_cell(tiny_cell(batch=4, pool_images=8), seed, 0.3, False,
                            t_start=time.perf_counter(), make_executor=make, peaks=cpu_peaks(),
                            weights_seed=seed)


def _broken(fault):
    def make(cell, weights_seed, x_cal, spans):
        fn = harness.program_executor(cell, weights_seed, x_cal, spans)
        return lambda x: fault(fn, x)
    return make


def _one_answer_altered(fn, x):
    y = fn(x)
    return y.at[1].set(jnp.roll(y[1], 1))


def _half_the_batch_left_out(fn, x):
    half = x.shape[0] // 2
    return fn(jnp.concatenate([x[:half], x[:half]]))


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 23, 4_000_000_007])
def test_the_int4_control_is_not_correct(seed):
    r = _run(harness.control_executor(4), seed)
    assert r["correct"] is False
    gap = r["checks"]["logprob_gap"]
    assert gap["value"] > 3 * gap["limit"]


@pytest.mark.parametrize("fault", [_one_answer_altered, _half_the_batch_left_out])
def test_a_broken_timed_path_is_not_correct(fault):
    r = _run(_broken(fault))
    assert r["correct"] is False
    assert r["checks"]["logprob_gap"]["value"] > r["checks"]["logprob_gap"]["limit"]


def test_the_unbroken_program_is_correct():
    assert _run(harness.program_executor)["correct"] is True


def test_the_gap_reads_logits_above_the_floor():
    import numpy as np
    p = np.array([0.5, 0.25, 1e-33, 0.0], np.float32)
    assert harness.logprob_gap(p, p.copy()) == 0.0
    # a probability flushed to zero on one side only reads nothing below the floor
    assert harness.logprob_gap(p, np.array([0.5, 0.25, 0.0, 1e-36], np.float32)) == 0.0
    assert harness.logprob_gap(p, np.array([0.25, 0.5, 1e-33, 0.0], np.float32)) == \
        pytest.approx(np.log(2))
    assert harness.logprob_gap(p, np.array([0.5, 0.25, 1e-20, 0.0], np.float32)) == \
        pytest.approx(np.log(1e-20 / harness.P_FLOOR), rel=1e-6)
    assert harness.logprob_gap(p, np.array([np.nan, 0.25, 0.0, 0.0], np.float32)) == np.inf
    assert harness.logprob_gap(p, p[:3]) == np.inf
