"""Plain reference of the synfire ring on the PE mesh.

Written from the paper's description (Hoeppner et al. 2021, Sec. VI-B,
Tables I-II) and the constants in the configuration file, importing
nothing of the program under test:

* the ring: PE p's 200 excitatory neurons project to PE p+1's 250
  neurons (fan-in 60, delay 10 ticks); its 50 inhibitory neurons project
  to its own 200 excitatory ones (fan-in 25, delay 8 ticks).  The
  connectivity is drawn from the seed with numpy's ``default_rng``, one
  ``choice(n_src, fan_in, replace=False)`` per target neuron, PE by PE
  (exc targets first, then inh) — the published construction;
* s16.15 fixed point: the membrane decay factor is the SpiNNaker 2
  exp accelerator's shift-add result (Partzsch et al. 2017, Mikaitis et
  al. 2018), the LIF update multiplies without overflow by splitting
  the membrane into high and low parts;
* the background current: a Gaussian of ``noise_sigma`` v_th made of
  four uniform 16-bit integers from JAX's threefry ``bits`` per tick
  (``fold_in(key, t)``, shape ``(2, P, N)``) and one float32 multiply,
  or ``kicks_per_tick`` shot-noise kicks at murmur3-hashed lanes;
* DVFS: the FIFO's spike count picks PL1-3 before the tick (Table II
  thresholds); Eq. (1) prices each PE's tick (Table I), in float64;
* NoC: PEs sit in snake order on a square mesh of 4-PE QPEs; each PE's
  packets (one per exc spike) take the X-first route to its ring
  successor; a link's load is the packets crossing it, and the traffic
  energy is 64 bits per link crossing at ``pj_per_bit_hop``.

The dynamics run as one ``lax.scan`` on the default device, dense and
in int32 (``precision="exact"``); ``precision="bf16"`` computes the
synaptic sums with bfloat16 weights and float32 accumulation — the
control that must fail the comparison.
"""
from __future__ import annotations

import math

import numpy as np

FRAC = 15
FX_ONE = 1 << FRAC


# ------------------------------------------------------------ fixed point

def to_fx(x: float) -> int:
    """float -> s16.15, rounding a float32 product half to even."""
    return int(np.round(np.float32(x) * np.float32(FX_ONE)))


def exp_accelerator(x_fx: int) -> int:
    """exp of an s16.15 argument by the accelerator's shift-add
    decomposition over ln(1 + 2^-k), k = 1..15, then a first-order
    remainder and the 2^n shift (Mikaitis et al. 2018)."""
    ln2 = int(round(math.log(2.0) * FX_ONE))
    n = x_fx // ln2
    r = x_fx - n * ln2
    y = FX_ONE
    for k in range(1, 16):
        lk = int(round(math.log1p(2.0 ** -k) * FX_ONE))
        if r >= lk:
            r -= lk
            y += y >> k
    y += (y * r) >> FRAC
    return y << n if n >= 0 else y >> -n


def lif_constants(s: dict) -> dict:
    return {"alpha": exp_accelerator(to_fx(-1.0 / s["tau_ms"])),
            "v_th": to_fx(s["v_th"]), "v_reset": to_fx(s["v_reset"]),
            "v_min": to_fx(s["v_min"]), "ref_ticks": int(s["ref_ticks"])}


# ------------------------------------------------------------ the network

class Net:
    """Connectivity of the ring, built from the seed (host, numpy)."""

    def __init__(self, sizes: dict, seed: int):
        s = sizes
        P, NE, NI = s["n_pes"], s["n_exc"], s["n_inh"]
        N = NE + NI
        rng = np.random.default_rng(seed)
        ff = np.zeros((P, NE, N), bool)
        inh = np.zeros((P, NI, NE), bool)
        for p in range(P):
            for tgt in range(N):
                ff[p, rng.choice(NE, s["fan_in_exc"], replace=False), tgt] = 1
            for tgt in range(NE):
                inh[p, rng.choice(NI, s["fan_in_inh"], replace=False),
                    tgt] = 1
        self.sizes = s
        self.ff, self.inh = ff, inh
        self.w_exc = int(np.round(np.float32(s["w_exc"]) * FX_ONE))
        self.w_inh = int(np.round(np.float32(s["w_inh"]) * FX_ONE))
        self.deg_ff = ff.sum(axis=2).astype(np.int32)      # (P, NE)
        self.deg_inh = inh.sum(axis=2).astype(np.int32)    # (P, NI)


# ------------------------------------------------------------- the mesh

def mesh_routes(sizes: dict) -> tuple:
    """(n_links, src_of_entry, link_of_entry, links_per_source) of the
    ring's X-first routes on the snake-placed square QPE mesh."""
    P, per_q = sizes["n_pes"], sizes["pes_per_qpe"]
    q = -(-P // per_q)
    W = int(math.ceil(math.sqrt(q)))
    H = -(-q // W)
    ids = {}
    for y in range(H):
        for x in range(W):
            if x + 1 < W:
                ids[(x, y), (x + 1, y)] = len(ids)
                ids[(x + 1, y), (x, y)] = len(ids)
            if y + 1 < H:
                ids[(x, y), (x, y + 1)] = len(ids)
                ids[(x, y + 1), (x, y)] = len(ids)
    snake = []
    for y in range(H):
        xs = range(W) if y % 2 == 0 else range(W - 1, -1, -1)
        snake.extend((x, y) for x in xs)
    coord = [snake[p // per_q] for p in range(P)]
    src, link = [], []
    for p in range(P):
        (x, y), (dx, dy) = coord[p], coord[(p + 1) % P]
        while x != dx:
            nx = x + (1 if dx > x else -1)
            src.append(p)
            link.append(ids[(x, y), (nx, y)])
            x = nx
        while y != dy:
            ny = y + (1 if dy > y else -1)
            src.append(p)
            link.append(ids[(x, y), (x, ny)])
            y = ny
    src, link = np.asarray(src, np.int64), np.asarray(link, np.int64)
    return len(ids), src, link, np.bincount(src, minlength=P)


# --------------------------------------------------------- the dynamics

def _fmix32(x):
    import jax.numpy as jnp
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def neuron_records(net: Net, drive: dict, noise_seed: int, n_ticks: int,
                   precision: str = "exact") -> dict:
    """Spikes, FIFO counts and synaptic events of ``n_ticks`` ticks, run
    on the default device.  ``drive`` is the traffic's background input
    (``noise_model`` and its parameters)."""
    import jax
    import jax.numpy as jnp
    s = net.sizes
    P, NE, NI = s["n_pes"], s["n_exc"], s["n_inh"]
    N = NE + NI
    d_e, d_i = int(s["delay_exc_ms"]), int(s["delay_inh_ms"])
    lif = lif_constants(s)
    model = drive.get("noise_model", "gauss")
    key = jax.random.PRNGKey(noise_seed)
    sigma_fx = int(round(drive.get("noise_sigma", s["noise_sigma"]) * FX_ONE))
    std_sum4 = float(np.sqrt((2.0 ** 32 - 1) / 3))
    stim_fx = int(round(s["stim_current"] * FX_ONE))
    if model == "shot":
        n_kicks = int(drive["kicks_per_tick"])
        kick_fx = int(round(drive["kick"] * FX_ONE))
    elif model != "gauss":
        raise ValueError(f"unknown noise model {model!r}")

    wdt = jnp.int32 if precision == "exact" else jnp.bfloat16
    if precision not in ("exact", "bf16"):
        raise ValueError(f"unknown precision {precision!r}")
    # the weights, degrees and key are arguments of the compiled scan,
    # not constants baked into it
    args = (jnp.asarray(np.where(net.ff, np.int32(net.w_exc), np.int32(0)),
                        wdt),
            jnp.asarray(np.where(net.inh, np.int32(net.w_inh), np.int32(0)),
                        wdt),
            jnp.asarray(net.deg_ff), jnp.asarray(net.deg_inh), key)

    def syn(arr, w, spec):
        if precision == "exact":
            return jnp.einsum(spec, arr, w)
        acc = jnp.einsum(spec, arr.astype(jnp.bfloat16), w,
                         preferred_element_type=jnp.float32)
        return jnp.round(acc).astype(jnp.int32)

    def fx_mul(a, b):
        return (a >> FRAC) * b + (((a & (FX_ONE - 1)) * b) >> FRAC)

    def simulate(w_ff, w_in, deg_ff, deg_in, key):
        if model == "shot":
            kd = jax.random.key_data(key).astype(jnp.uint32).ravel()
            seed32 = _fmix32(kd[-1] ^ _fmix32(kd[0]))

        def tick(carry, t):
            v, ref, exc_hist, inh_hist = carry
            arr_e = exc_hist[t % d_e].astype(jnp.int32)       # (P, NE)
            arr_i = inh_hist[t % d_i].astype(jnp.int32)       # (P, NI)
            i_syn = syn(arr_e, w_ff, "pe,pen->pn")
            i_syn = i_syn.at[:, :NE].add(syn(arr_i, w_in, "pi,pie->pe"))
            if model == "gauss":
                bits = jax.random.bits(jax.random.fold_in(key, t),
                                       (2, P, N), jnp.uint32)
                halves = (bits & 0xFFFF) + (bits >> 16)
                centred = ((halves[0] + halves[1]).astype(jnp.int32)
                           - 2 * 0xFFFF)
                i_syn = i_syn + jnp.round(
                    centred.astype(jnp.float32)
                    * jnp.float32(sigma_fx / std_sum4)).astype(jnp.int32)
            else:
                c = (t.astype(jnp.uint32) * jnp.uint32(n_kicks)
                     + jnp.arange(n_kicks, dtype=jnp.uint32))
                lanes = (_fmix32(c ^ seed32) % jnp.uint32(P * N)).astype(
                    jnp.int32)
                i_syn = i_syn.at[lanes // N, lanes % N].add(kick_fx)
            i_syn = i_syn.at[0, :NE].add(
                jnp.where(t < s["stim_ticks"], stim_fx, 0))
            active = ref <= 0
            v1 = jnp.maximum(fx_mul(v, lif["alpha"]) + i_syn, lif["v_min"])
            spike = active & (v1 >= lif["v_th"])
            v = jnp.where(spike, lif["v_reset"], jnp.where(active, v1, v))
            ref = jnp.where(spike, lif["ref_ticks"], jnp.maximum(ref - 1, 0))
            spk_e, spk_i = spike[:, :NE], spike[:, NE:]
            exc_hist = exc_hist.at[t % d_e].set(
                jnp.roll(spk_e, 1, axis=0).astype(jnp.int8))
            inh_hist = inh_hist.at[t % d_i].set(spk_i.astype(jnp.int8))
            rec = {"spikes_exc": spk_e.astype(jnp.int8),
                   "spikes_inh": spk_i.astype(jnp.int8),
                   "n_fifo": arr_e.sum(axis=1) + arr_i.sum(axis=1),
                   "syn_events": ((arr_e * deg_ff).sum(axis=1)
                                  + (arr_i * deg_in).sum(axis=1))}
            return (v, ref, exc_hist, inh_hist), rec

        init = (jnp.zeros((P, N), jnp.int32), jnp.zeros((P, N), jnp.int32),
                jnp.zeros((d_e, P, NE), jnp.int8),
                jnp.zeros((d_i, P, NI), jnp.int8))
        return jax.lax.scan(tick, init, jnp.arange(n_ticks))[1]

    out = jax.jit(simulate)(*args)
    del args
    return {k: np.asarray(v) for k, v in out.items()}


def mesh_records(rec: dict, sizes: dict, energy: dict, noc: dict) -> dict:
    """DVFS levels, Eq. (1) energies and NoC accounting from the neuron
    records (float64 on the host)."""
    n_fifo = rec["n_fifo"].astype(np.int64)
    syn = rec["syn_events"].astype(np.float64)
    pl = (n_fifo >= sizes["l_th1"]).astype(np.int64) + (
        n_fifo >= sizes["l_th2"])
    f = np.asarray(energy["freq_hz"], np.float64)
    p_bl = np.asarray(energy["p_baseline_w"], np.float64)
    e_n = np.asarray(energy["e_neuron_j"], np.float64)
    e_s = np.asarray(energy["e_synapse_j"], np.float64)
    t_sys = energy["t_sys_s"]
    N = sizes["n_exc"] + sizes["n_inh"]
    cycles = (energy["cycles_overhead"] + energy["cycles_per_neuron"] * N
              + energy["cycles_per_syn"] * syn)
    t_sp = np.minimum(cycles / f[pl], t_sys)
    out = {
        "pl": pl,
        "e_dvfs_baseline": p_bl[pl] * t_sp + p_bl[0] * (t_sys - t_sp),
        "e_dvfs_neuron": e_n[pl] * N,
        "e_dvfs_synapse": e_s[pl] * syn,
        "t_sp": t_sp,
        "e_pl3_baseline": np.full(pl.shape, p_bl[2] * t_sys),
        "e_pl3_neuron": np.full(pl.shape, e_n[2] * N),
        "e_pl3_synapse": e_s[2] * syn,
    }
    packets = rec["spikes_exc"].sum(axis=2, dtype=np.int64)     # (T, P)
    n_links, src, link, per_src = mesh_routes(sizes)
    loads = np.zeros((n_links, packets.shape[0]), np.float64)
    np.add.at(loads, link, packets[:, src].T)
    loads = loads.T
    active = (packets > 0).sum(axis=1)
    touched = (loads > 0).sum(axis=1).astype(np.float64)
    out.update({
        "packets": packets,
        "link_load": loads,
        "link_flits": loads,                  # spike packets: one flit
        "e_noc": (packets * per_src).sum(axis=1) * noc["spike_packet_bits"]
        * noc["pj_per_bit_hop"] * 1e-12,
        "active_sources": active,
        "active_frac": active / sizes["n_pes"],
        "touched_links": touched,
        "touched_links_onchip": touched,
    })
    return out


def records(config: dict, traffic: dict, build_seed: int, noise_seed: int,
            n_ticks: int, precision: str = "exact", net: Net | None = None
            ) -> tuple:
    """``(records, net)``: every per-tick record of one job (``net``, if
    given, is this seed's network, built before)."""
    net = net or Net(config["sizes"], build_seed)
    rec = neuron_records(net, traffic.get("drive", {}), noise_seed, n_ticks,
                         precision)
    rec.update(mesh_records(rec, config["sizes"], config["energy"],
                            config["noc"]))
    return rec, net


# ------------------------------------------------------------ comparison

def compare(ref: dict, got: dict) -> dict:
    """The numbers ``correct`` is decided by.

    * ``int_mismatch``: entries of integer-valued records (spikes,
      packets, FIFO counts, DVFS levels, synaptic events, link loads and
      flits, active sources, touched links) that differ; exact, so its
      limit is 0.  A missing or extra record, or a shape that differs,
      counts as every entry of it.
    * ``float_rel_gap``: the widest relative gap of the float records
      (energies and busy times entry by entry, ``active_frac`` against
      its largest value)."""
    mismatch, gap = 0, 0.0
    for k in sorted(set(ref) | set(got)):
        if k not in ref or k not in got or np.shape(ref[k]) != np.shape(
                got[k]):
            mismatch += int(np.size(ref.get(k, got.get(k))))
            continue
        a, b = ref[k], got[k]
        if np.issubdtype(a.dtype, np.integer) or np.all(a == np.round(a)):
            mismatch += int(np.count_nonzero(
                a.astype(np.float64) != b.astype(np.float64)))
            continue
        a64, b64 = a.astype(np.float64), b.astype(np.float64)
        if k.startswith("e_") or k == "t_sp":
            # an entry the reference has at 0 and the run does not is
            # off by all of itself: a relative gap of 1
            den = np.where(a64 == 0, np.abs(b64), np.abs(a64))
            rel = np.abs(b64 - a64)[den > 0] / den[den > 0]
        else:
            rel = np.abs(b64 - a64) / max(float(np.abs(a64).max()), 1e-300)
        gap = max(gap, float(rel.max(initial=0.0)))
    return {"int_mismatch": mismatch, "float_rel_gap": gap}


def tick_work(rec: dict, net: Net) -> dict:
    """Needed work per tick (``bench.work.synfire_tick_work``)."""
    from bench.work import synfire_tick_work
    s = net.sizes
    return synfire_tick_work(rec, net.deg_ff, net.deg_inh,
                             int(s["delay_exc_ms"]), int(s["delay_inh_ms"]))
