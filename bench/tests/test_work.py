"""``synfire_tick_work`` against a count made by hand at 8 PEs."""
import numpy as np

from bench.work import (OPS_PER_SYN_EVENT, STATE_BYTES_PER_NEURON,
                        WEIGHT_BYTES, least_time_s, record_bytes,
                        synfire_tick_work)

P, NE, NI, T = 8, 200, 50, 20
D_EXC, D_INH = 10, 8


def _records():
    se = np.zeros((T, P, NE), np.int8)
    si = np.zeros((T, P, NI), np.int8)
    se[0, 3, 5] = 1          # delivered at tick 10 to PE 4
    se[9, 7, 0] = 1          # delivered at tick 19 to PE 0 (ring wraps)
    se[10, 1, 1] = 1         # due at tick 20: after the job, not counted
    si[2, 6, 7] = 1          # delivered at tick 10 to PE 6's exc neurons
    si[12, 6, 7] = 1         # due at tick 20: not counted
    return {"spikes_exc": se, "spikes_inh": si}


def test_synaptic_events_by_hand():
    deg_ff = np.zeros((P, NE), np.int32)
    deg_inh = np.zeros((P, NI), np.int32)
    deg_ff[4, 5] = 61        # PE 3's neuron 5 drives 61 synapses on PE 4
    deg_ff[0, 0] = 59        # PE 7's neuron 0 drives 59 on PE 0
    deg_ff[2, 1] = 1000      # the spike that arrives too late
    deg_ff[3, 5] = 7         # the wrong PE: must not be read
    deg_inh[6, 7] = 24
    w = synfire_tick_work(_records(), deg_ff, deg_inh, D_EXC, D_INH)
    events = 61 + 59 + 24
    assert w["syn_events_per_tick"] == events / T
    assert w["ops_per_tick"] == OPS_PER_SYN_EVENT * events / T
    neurons = P * (NE + NI)
    state = 2 * neurons * STATE_BYTES_PER_NEURON + 2 * neurons / 8
    # two 0/1 records of T*P*NE and T*P*NI entries, one bit each
    recs = (T * P * NE + T * P * NI) / 8 / T
    assert w["bytes_per_tick"] == WEIGHT_BYTES * events / T + state + recs


def test_record_bytes_takes_the_narrowest_exact_width():
    assert record_bytes(np.array([0, 1, 1, 0], np.int32)) == 0.5
    assert record_bytes(np.array([0, 5, -3], np.int32)) == 3
    assert record_bytes(np.array([0.0, 200.0, 3.0], np.float32)) == 6
    assert record_bytes(np.array([70000, 1], np.int64)) == 8
    assert record_bytes(np.array([0.5, 1.0], np.float32)) == 8


def test_least_time_takes_the_slower_bound():
    peaks = {"int8_ops_per_s": 400.0, "bf16_flops_per_s": 200.0,
             "hbm_bytes_per_s": 100.0}
    assert least_time_s(800.0, 100.0, peaks) == 2.0
    assert least_time_s(400.0, 300.0, peaks) == 3.0
