"""Hypothesis property suite for the plasticity rules.

Invariants, over randomized spike trains / rule parameters:

* STDP weights never leave their declared [w_min, w_max] bounds;
* zero error is an EXACT PES fixed point (decoders bitwise unchanged);
* the s16.15 trace decay (exp-accelerator kernel + hi/lo fixed-point
  multiply) tracks the float oracle within s16.15-class tolerance;
* the fx STDP weight trajectory tracks the float oracle;
* the explog ``impl`` knob is representation-only: "ref" and "pallas"
  agree bitwise.
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, strategies as st

from repro.kernels.explog.ops import fx_exp, to_fx
from repro.kernels.explog.ref import FX_ONE
from repro.learn import (PES, STDP, pes_step, stdp_step_fx, stdp_step_ref,
                         trace_step_fx, trace_step_ref, trace_to_hz)


@st.composite
def spike_trains(draw, max_t=24, max_n=12):
    T = draw(st.integers(2, max_t))
    n = draw(st.integers(1, max_n))
    bits = draw(st.lists(st.integers(0, 1), min_size=T * n,
                         max_size=T * n))
    return np.asarray(bits, np.float32).reshape(T, n)


@given(spikes=spike_trains(), tau=st.floats(2.0, 50.0))
def test_fx_trace_decay_matches_float_oracle(spikes, tau):
    T, n = spikes.shape
    tr_fx = jnp.zeros(n, jnp.int32)
    tr_f = jnp.zeros(n, jnp.float32)
    for t in range(T):
        s = jnp.asarray(spikes[t])
        tr_fx = trace_step_fx(tr_fx, s, tau)
        tr_f = trace_step_ref(tr_f, s, tau)
    got = np.asarray(tr_fx, np.float64) / FX_ONE
    want = np.asarray(tr_f, np.float64)
    # decay factor is accurate to ~2^-12 relative per step; across T
    # steps the drift stays bounded by the accumulated trace magnitude
    tol = 2e-3 * max(float(want.max()), 1.0) * T + 2 / FX_ONE
    assert np.abs(got - want).max() <= tol


@given(spikes=spike_trains(max_n=6),
       a_plus=st.floats(0.0, 0.1), a_minus=st.floats(0.0, 0.1),
       w_lo=st.floats(0.0, 0.4), w_span=st.floats(0.05, 0.6),
       seed=st.integers(0, 2**16))
def test_stdp_weights_stay_within_declared_bounds(spikes, a_plus, a_minus,
                                                  w_lo, w_span, seed):
    T, n_pre = spikes.shape
    n_post = 3
    rule = STDP(a_plus=a_plus, a_minus=a_minus, w_min=w_lo,
                w_max=w_lo + w_span, w_init=w_lo + w_span / 2)
    rng = np.random.default_rng(seed)
    post = (rng.random((T, n_post)) < 0.3).astype(np.float32)
    w = jnp.full((n_pre, n_post), int(round(rule.w_init * FX_ONE)),
                 jnp.int32)
    ptr = jnp.zeros(n_pre, jnp.int32)
    qtr = jnp.zeros(n_post, jnp.int32)
    for t in range(T):
        w, ptr, qtr = stdp_step_fx(w, ptr, qtr, jnp.asarray(spikes[t]),
                                   jnp.asarray(post[t]), rule)
    wf = np.asarray(w, np.float64) / FX_ONE
    assert wf.min() >= rule.w_min - 1 / FX_ONE
    assert wf.max() <= rule.w_max + 1 / FX_ONE


@given(spikes=spike_trains(max_t=16, max_n=5), seed=st.integers(0, 2**16))
def test_fx_stdp_tracks_float_oracle(spikes, seed):
    T, n_pre = spikes.shape
    n_post = 2
    rule = STDP()
    rng = np.random.default_rng(seed)
    post = (rng.random((T, n_post)) < 0.4).astype(np.float32)
    w_fx = jnp.full((n_pre, n_post), int(round(rule.w_init * FX_ONE)),
                    jnp.int32)
    ptr_fx = jnp.zeros(n_pre, jnp.int32)
    qtr_fx = jnp.zeros(n_post, jnp.int32)
    w_f = jnp.full((n_pre, n_post), np.float32(rule.w_init))
    ptr_f = jnp.zeros(n_pre, jnp.float32)
    qtr_f = jnp.zeros(n_post, jnp.float32)
    for t in range(T):
        pre_t, post_t = jnp.asarray(spikes[t]), jnp.asarray(post[t])
        w_fx, ptr_fx, qtr_fx = stdp_step_fx(w_fx, ptr_fx, qtr_fx,
                                            pre_t, post_t, rule)
        w_f, ptr_f, qtr_f = stdp_step_ref(w_f, ptr_f, qtr_f,
                                          pre_t, post_t, rule)
    got = np.asarray(w_fx, np.float64) / FX_ONE
    want = np.asarray(w_f, np.float64)
    assert np.abs(got - want).max() <= 5e-3 * T + 2 / FX_ONE


@given(n=st.integers(1, 64), d=st.integers(1, 4),
       lr=st.floats(1e-7, 1e-2), seed=st.integers(0, 2**16))
def test_pes_zero_error_is_exact_fixed_point(n, d, lr, seed):
    rng = np.random.default_rng(seed)
    dec = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    act = jnp.asarray(np.abs(rng.standard_normal(n)) * 200, jnp.float32)
    rule = PES(learning_rate=lr)
    out = pes_step(dec, act, jnp.zeros(d), rule, n)
    assert np.array_equal(np.asarray(out), np.asarray(dec))
    # ...and a nonzero error moves the decoders against its sign, where
    # the float32 step lr/n * a is at least one ulp of the decoder (a
    # smaller step rounds away: d - step == d)
    err = jnp.ones(d)
    out2 = np.asarray(pes_step(dec, act, err, rule, n))
    moved = np.asarray(dec) - out2
    step = np.float32(lr / n) * np.asarray(act)[:, None]
    resolved = step >= np.spacing(np.abs(np.asarray(dec)))
    assert (moved >= 0).all()
    assert (moved[resolved] > 0).all()


@given(xs=st.lists(st.floats(-10.0, 10.0), min_size=1, max_size=64))
def test_explog_impl_knob_is_bitwise(xs):
    x = to_fx(jnp.asarray(np.asarray(xs, np.float32)))
    assert np.array_equal(np.asarray(fx_exp(x, impl="ref")),
                          np.asarray(fx_exp(x, impl="pallas")))


def test_trace_to_hz_steady_state():
    """A constant-rate train's trace converges to rate/(1-alpha); the Hz
    conversion recovers the rate."""
    tau = 20.0
    tr = jnp.zeros(1, jnp.int32)
    for _ in range(400):
        tr = trace_step_fx(tr, jnp.ones(1), tau)
    hz = float(trace_to_hz(tr, tau)[0])
    assert hz == pytest.approx(1000.0, rel=0.02)   # 1 spike/tick = 1 kHz
