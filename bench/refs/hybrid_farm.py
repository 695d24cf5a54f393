"""Plain reference of the hybrid NEF -> event-MAC farm on a chip board.

Written from the paper's description (Hoeppner et al. 2021, Sec. II and
Sec. VI-C, Figs. 19-21; the board of Mayr et al. 2019, arXiv:1911.02385)
and the constants in the configuration file, importing nothing of the
program under test:

* the channel: a NEF ensemble of ``n_neurons`` s16.15 LIF neurons on
  one PE (encoders, intercepts and maximum rates drawn from the seed
  with numpy's ``default_rng``, gains and biases by Nengo's LIF rate
  inverse) represents a ``dims``-D sine drive, encoded on the int8 MAC
  array (drive and encoders quantized symmetrically, per tick and per
  neuron); its spike vector crosses the NoC as one graded packet
  (``bits_per_spike`` per spike) to an MLP PE, which on the next tick
  multiplies the arrived spikes into ``hidden`` units through int8
  weights (drawn from the same seed, dequantized per column) — the
  event-triggered MAC layer.  All channels share one ensemble and one
  weight draw; channel k reads the drive ``k * phase_step_ticks`` ticks
  ahead;
* DVFS: each PE's FIFO count (the NEF PE's ``n_neurons`` inputs, the MLP
  PE's arrived spikes) picks PL1-3 before the tick (Table II
  thresholds); Eq. (1) prices each PE's tick (Table I) with the decode
  adds as synaptic events on the NEF PE, and the MLP's MACs at the MAC
  array's TOPS/W (Fig. 15), in float64;
* the board: ``chips_x x chips_y`` chips of ``chip_width x chip_height``
  QPEs.  The populations (every NEF PE, then every MLP PE) fill the
  chips in snake order over the chip grid, ``pes_per_chip`` each, and
  each chip's PEs in snake order over its QPEs.  A packet takes the
  X-first route on its own chip to the mid-edge border port of its
  first chip-to-chip hop, X-first from chip to chip, and X-first from
  each entry port on to the next exit port or its destination.  Links
  are numbered chip by chip (each chip's mesh links as on one chip),
  then the chip-to-chip links; a link's load is the packets crossing
  it, its flits ``ceil(bits / flit_payload_bits)`` per graded packet,
  and each crossing costs ``flit_bits`` per flit at its tier's pJ/bit.

The neuron dynamics and the MLP run as one ``lax.scan`` on the default
device, the MLP in float32 under ``jax.default_matmul_precision
("highest")`` (``precision="exact"``); ``precision="bf16"`` multiplies
bfloat16 spikes by bfloat16 weights with float32 accumulation — the
control that must fail the comparison.  The float32 steps of the drive
encoding and the weight quantization run op by op on the default
device, in the order written down here, so that the integer records
can be compared exactly.
"""
from __future__ import annotations

import numpy as np

from bench.refs.synfire import FX_ONE, compare, exp_accelerator, to_fx
from bench.work import record_bytes

__all__ = ["Net", "records", "compare", "tick_work", "board_routes"]

EAST, WEST, NORTH, SOUTH = (1, 0), (-1, 0), (0, 1), (0, -1)


# ------------------------------------------------------------ the network

def _quantize(x, axis: int, qmax: int):
    """Symmetric int8 quantization of a float32 device array along
    ``axis``: ``(q, scale)``, one scale per slice of the other axis."""
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax).astype(jnp.int8)
    return q, jnp.squeeze(scale, axis=axis).astype(jnp.float32)


class Net:
    """The shared ensemble, MLP weights and board routes of one seed."""

    def __init__(self, sizes: dict, seed: int):
        import jax.numpy as jnp
        s = sizes
        N, D, H = s["n_neurons"], s["dims"], s["hidden"]
        qmax = 2 ** (s["weight_bits"] - 1) - 1
        rng = np.random.default_rng(seed)
        enc = rng.standard_normal((N, D))
        enc /= np.linalg.norm(enc, axis=1, keepdims=True)
        intercepts = rng.uniform(*s["intercept_range"], N)
        max_rates = rng.uniform(*s["max_rate_range_hz"], N)
        # the LIF rate curve r(J) = 1 / (tau_ref + tau_rc ln(1 + 1/(J-1)))
        # reaches max_rate at x = 1 and 0 at x = intercept
        t_ref, t_rc = s["tau_ref_s"], s["tau_rc_s"]
        gains = (1.0 - 1.0 / (1.0 - np.exp((t_ref * max_rates - 1.0)
                                           / (t_rc * max_rates)))) \
            / (intercepts - 1.0)
        self.biases = 1.0 - gains * intercepts
        self.enc_q, self.enc_scale = _quantize(
            jnp.asarray((gains[:, None] * enc).T, jnp.float32), 0, qmax)
        w = jnp.asarray(np.random.default_rng(seed).standard_normal((N, H))
                        * s["mlp_weight_sigma"], jnp.float32)
        wq, w_scale = _quantize(w, 0, qmax)
        self.w_eff = wq.astype(jnp.float32) * w_scale[None, :]
        self.lif = {"alpha": exp_accelerator(to_fx(-1.0 / s["tau_ms"])),
                    "v_th": to_fx(s["v_th"]),
                    "v_reset": to_fx(s["v_reset"]),
                    "ref_ticks": int(s["ref_ticks"])}
        self.sizes = s
        self.routes = board_routes(s)

    def drive_table(self, drive: dict):
        """(table_ticks, N) int32 s16.15 drive per tick: the sine,
        quantized per tick, times the quantized encoders on the MAC
        array (int32), rescaled, plus the bias current, as the membrane
        increment ``(1 - alpha) J`` of one 1 ms tick."""
        import jax.numpy as jnp
        t = np.arange(drive["table_ticks"])
        x = drive["amplitude"] * np.sin(2 * np.pi * t / drive["period_ticks"])
        qmax = 2 ** (self.sizes["weight_bits"] - 1) - 1
        xq, x_scale = _quantize(jnp.asarray(x[:, None], jnp.float32), 1, qmax)
        acc = (xq.astype(jnp.int32)[:, :, None]              # (T, D, N)
               * self.enc_q.astype(jnp.int32)[None]).sum(axis=1)
        J = (acc.astype(jnp.float32) * x_scale[:, None]
             * self.enc_scale[None, :])
        J = J + jnp.asarray(self.biases, jnp.float32)[None, :]
        alpha = self.lif["alpha"] / FX_ONE
        return jnp.round(J * (1.0 - alpha) * FX_ONE).astype(jnp.int32)


# ------------------------------------------------------------- the board

def _snake(width: int, height: int) -> list:
    """(x, y) cells of a width x height grid in boustrophedon order."""
    out = []
    for y in range(height):
        xs = range(width) if y % 2 == 0 else range(width - 1, -1, -1)
        out.extend((x, y) for x in xs)
    return out


def _mesh_link_ids(width: int, height: int) -> dict:
    """Directed links of one mesh, numbered row by row: for each cell,
    east and back, then north and back."""
    ids = {}
    for y in range(height):
        for x in range(width):
            if x + 1 < width:
                ids[(x, y), (x + 1, y)] = len(ids)
                ids[(x + 1, y), (x, y)] = len(ids)
            if y + 1 < height:
                ids[(x, y), (x, y + 1)] = len(ids)
                ids[(x, y + 1), (x, y)] = len(ids)
    return ids


def _xy_path(a: tuple, b: tuple) -> list:
    """Hops ((x, y), (x', y')) of the X-first route from a to b."""
    (x, y), hops = a, []
    while x != b[0]:
        nx = x + (1 if b[0] > x else -1)
        hops.append(((x, y), (nx, y)))
        x = nx
    while y != b[1]:
        ny = y + (1 if b[1] > y else -1)
        hops.append(((x, y), (x, ny)))
        y = ny
    return hops


def board_routes(s: dict) -> dict:
    """Placement and routes of the farm on the board: ``n_links``,
    ``xchip`` (0/1 per link), and per channel the link ids of its
    packet's route (``link``, ``src`` the channel of each entry) and its
    on-chip and chip-to-chip link counts (``n_on``, ``n_x``)."""
    bx, by = s["chips_x"], s["chips_y"]
    W, H, per_q = s["chip_width"], s["chip_height"], s["pes_per_qpe"]
    per_chip, K = s["pes_per_chip"], s["n_pairs"]
    if per_chip != W * H * per_q or 2 * K != bx * by * per_chip:
        raise ValueError("the farm must fill the board: 2 * n_pairs PEs")
    local = _mesh_link_ids(W, H)
    n_on = bx * by * len(local)
    xids = {}                               # (chip xy, direction) -> id
    for cy in range(by):
        for cx in range(bx):
            if cx + 1 < bx:
                xids[(cx, cy), EAST] = n_on + len(xids)
                xids[(cx + 1, cy), WEST] = n_on + len(xids)
            if cy + 1 < by:
                xids[(cx, cy), NORTH] = n_on + len(xids)
                xids[(cx, cy + 1), SOUTH] = n_on + len(xids)
    port = {EAST: (W - 1, H // 2), WEST: (0, H // 2),
            NORTH: (W // 2, H - 1), SOUTH: (W // 2, 0)}
    chips, qpes = _snake(bx, by), _snake(W, H)

    def where(p):                # population p -> (chip xy, QPE xy)
        return chips[p // per_chip], qpes[(p % per_chip) // per_q]

    def on_chip(chip, a, b):
        base = (chip[1] * bx + chip[0]) * len(local)
        return [base + local[h] for h in _xy_path(a, b)]

    src, link, n_on_k, n_x_k = [], [], [], []
    for k in range(K):
        (cs, qs), (cd, qd) = where(k), where(K + k)
        ids, at, here = [], qs, cs
        for a, b in _xy_path(cs, cd):       # chip by chip, X first
            d = (b[0] - a[0], b[1] - a[1])
            ids += on_chip(here, at, port[d]) + [xids[a, d]]
            here, at = b, port[(-d[0], -d[1])]
        ids += on_chip(here, at, qd)
        n_x = len(_xy_path(cs, cd))
        src += [k] * len(ids)
        link += ids
        n_on_k.append(len(ids) - n_x)
        n_x_k.append(n_x)
    xchip = np.zeros(n_on + len(xids), bool)
    xchip[n_on:] = True
    return {"n_links": n_on + len(xids), "xchip": xchip,
            "src": np.asarray(src, np.int64),
            "link": np.asarray(link, np.int64),
            "n_on": np.asarray(n_on_k, np.float64),
            "n_x": np.asarray(n_x_k, np.float64)}


# --------------------------------------------------------- the dynamics

def channel_records(net: Net, drive: dict, n_ticks: int,
                    precision: str = "exact") -> dict:
    """Per tick and channel: the NEF PE's spike count ``n_spk`` (T, K)
    and the MLP PE's ``hidden_out`` (T, K, hidden), run on the default
    device."""
    import jax
    import jax.numpy as jnp
    if precision not in ("exact", "bf16"):
        raise ValueError(f"unknown precision {precision!r}")
    s, lif = net.sizes, net.lif
    K, N = s["n_pairs"], s["n_neurons"]
    table = net.drive_table(drive)
    T = table.shape[0]
    offsets = jnp.asarray((np.arange(K) * drive["phase_step_ticks"]) % T)
    w = net.w_eff if precision == "exact" else net.w_eff.astype(jnp.bfloat16)

    def mlp(arr, w):
        if precision == "exact":
            return jnp.matmul(arr, w)
        return jnp.matmul(arr.astype(jnp.bfloat16), w,
                          preferred_element_type=jnp.float32)

    def fx_mul(a, b):
        return (a >> 15) * b + (((a & (FX_ONE - 1)) * b) >> 15)

    def simulate(table, offsets, w):
        def tick(carry, t):
            v, ref, arrived = carry
            i_in = table[(t + offsets) % T]                     # (K, N)
            active = ref <= 0
            v1 = fx_mul(v, lif["alpha"]) + i_in
            spike = active & (v1 >= lif["v_th"])
            v = jnp.where(spike, lif["v_reset"], jnp.where(active, v1, v))
            ref = jnp.where(spike, lif["ref_ticks"],
                            jnp.maximum(ref - 1, 0))
            h = mlp(arrived, w)              # last tick's spike vectors
            return (v, ref, spike.astype(jnp.float32)), {
                "n_spk": spike.sum(axis=1, dtype=jnp.int32),
                "hidden_out": h}

        init = (jnp.zeros((K, N), jnp.int32), jnp.zeros((K, N), jnp.int32),
                jnp.zeros((K, N), jnp.float32))
        return jax.lax.scan(tick, init, jnp.arange(n_ticks))[1]

    with jax.default_matmul_precision("highest"):
        out = jax.jit(simulate)(table, offsets, w)
    return {k: np.asarray(v) for k, v in out.items()}


def pe_records(ch: dict, sizes: dict, energy: dict, noc: dict,
               routes: dict) -> dict:
    """Per-PE DVFS levels, Eq. (1) energies and board NoC accounting
    from the channel records (float64 on the host).  PEs are numbered
    in population order: the K NEF PEs, then the K MLP PEs."""
    s = sizes
    K, N, D, H = s["n_pairs"], s["n_neurons"], s["dims"], s["hidden"]
    n_spk = ch["n_spk"].astype(np.float64)                      # (T, K)
    T = n_spk.shape[0]
    n_arr = np.concatenate([np.zeros((1, K)), n_spk[:-1]])
    zero = np.zeros((T, K))

    def pes(nef, mlp):
        return np.concatenate([nef, mlp], axis=1)               # (T, 2K)

    bits_out = s["bits_per_spike"] * n_spk
    fifo = pes(np.full((T, K), float(N)), n_arr)
    pl = ((fifo >= s["l_th1"]).astype(np.int64)
          + (fifo >= s["l_th2"]).astype(np.int64))
    n_neur = pes(np.full((T, K), float(N)), zero)
    snn_ev = pes(n_spk * D, zero)
    macs = n_arr * H
    e_mac = pes(zero, 2.0 * macs / (energy["mac_tops_per_w"] * 1e12))
    f = np.asarray(energy["freq_hz"], np.float64)
    p_bl = np.asarray(energy["p_baseline_w"], np.float64)
    e_n = np.asarray(energy["e_neuron_j"], np.float64)
    e_s = np.asarray(energy["e_synapse_j"], np.float64)
    t_sys = energy["t_sys_s"]
    cycles = (energy["cycles_overhead"] + energy["cycles_per_neuron"] * n_neur
              + energy["cycles_per_syn"] * snn_ev)
    t_sp = np.minimum(cycles / f[pl], t_sys)
    packets = pes((n_spk > 0).astype(np.float64), zero)
    out = {
        "packets": packets,
        "payload_bits": pes(bits_out, zero),
        "graded_bits_out": pes(bits_out, zero),
        "graded_bits_in": pes(zero, s["bits_per_spike"] * n_arr),
        "pl": pl,
        "n_fifo": fifo,
        "syn_events": pes(n_spk * D, macs),
        "n_spk": n_spk.sum(axis=1),
        "hidden_out": ch["hidden_out"],
        "e_dvfs_baseline": p_bl[pl] * t_sp + p_bl[0] * (t_sys - t_sp),
        "e_dvfs_neuron": e_n[pl] * n_neur,
        "e_dvfs_synapse": e_s[pl] * snn_ev + e_mac,
        "e_pl3_baseline": np.full(pl.shape, p_bl[2] * t_sys),
        "e_pl3_neuron": e_n[2] * n_neur,
        "e_pl3_synapse": e_s[2] * snn_ev + e_mac,
    }
    # the board NoC: one graded packet per NEF PE that spiked
    sent = n_spk > 0                                            # (T, K)
    flits = np.where(sent, np.ceil(bits_out / noc["flit_payload_bits"]), 0)
    loads = np.zeros((routes["n_links"], T))
    flit_loads = np.zeros((routes["n_links"], T))
    np.add.at(loads, routes["link"], sent.T[routes["src"]])
    np.add.at(flit_loads, routes["link"], flits.T[routes["src"]])
    loads, flit_loads = loads.T, flit_loads.T
    wire_bits = flits * noc["flit_bits"]
    x = routes["xchip"]
    e_x = (wire_bits * routes["n_x"]).sum(axis=1) * noc[
        "xchip_pj_per_bit_hop"] * 1e-12
    e_on = (wire_bits * routes["n_on"]).sum(axis=1) * noc[
        "pj_per_bit_hop"] * 1e-12
    active = sent.sum(axis=1)
    hit = loads > 0
    out.update({
        "link_load": loads,
        "link_flits": flit_loads,
        "e_noc": e_on + e_x,
        "e_noc_xchip": e_x,
        "load_xchip": loads[:, x].sum(axis=1),
        "flits_xchip": flit_loads[:, x].sum(axis=1),
        "active_sources": active,
        "active_frac": active / (2 * K),
        "touched_links": hit.sum(axis=1),
        "touched_links_onchip": hit[:, ~x].sum(axis=1),
        "touched_links_xchip": hit[:, x].sum(axis=1),
    })
    return out


def records(config: dict, traffic: dict, build_seed: int, noise_seed: int,
            n_ticks: int, precision: str = "exact", net: Net | None = None
            ) -> tuple:
    """``(records, net)``: every per-tick record of one job (``net``, if
    given, is this seed's network, built before).  The farm has no
    noise: ``noise_seed`` changes nothing."""
    net = net or Net(config["sizes"], build_seed)
    ch = channel_records(net, traffic["drive"], n_ticks, precision)
    return pe_records(ch, config["sizes"], config["energy"], config["noc"],
                      net.routes), net


# ------------------------------------------------------------ comparison
#
# ``compare`` is the synfire reference's: integer-valued records (levels,
# FIFO counts, events, packets, payload bits, every link's load and
# flits, the tier totals, touched links) entry by entry and exactly;
# energies entry by entry and ``hidden_out`` and ``active_frac`` against
# their largest value, by relative gap.  The board's link ids are
# enumerated here in the program's order, so link records are compared
# link by link.

# the membrane of a farm neuron is unbounded below (its bias current can
# sit far under threshold), so it needs all 32 bits; the refractory count
# (0..2) takes 2 bits, the spike of the 1-tick transport buffer 1 bit
STATE_BYTES_PER_NEURON = 4 + 0.25
OPS_PER_MAC = 2                  # one multiply-add


def tick_work(rec: dict, net: Net) -> dict:
    """Operations and bytes one tick of the farm needs, averaged over
    the ``T`` ticks of one job's records ``rec``.

    1. The MLP PEs' MACs: each spike that arrives inside the job (sent
       on ticks 0..T-2) drives ``hidden`` multiply-adds.
    2. Every neuron's state read once and written once, and its spike
       written to and read from the transport buffer once, as one bit
       each way.
    3. Every record written once (``bench.work.record_bytes``).
    """
    s = net.sizes
    n_spk = np.asarray(rec["n_spk"], np.float64)
    T = n_spk.shape[0]
    arrived = float(n_spk[:-1].sum())
    n_neurons = s["n_pairs"] * s["n_neurons"]
    state = 2 * n_neurons * STATE_BYTES_PER_NEURON + 2 * n_neurons / 8
    records = sum(record_bytes(v) for v in rec.values()) / T
    return {
        "spikes_arrived_per_tick": arrived / T,
        "ops_per_tick": OPS_PER_MAC * s["hidden"] * arrived / T,
        "bytes_per_tick": state + records,
    }
