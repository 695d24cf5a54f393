"""Mesh NoC traffic model (paper Sec. III-A/B at chip scale).

The chip is a W x H mesh of QPEs (4 PEs each) joined by directed links.
Spike delivery is multicast: the router duplicates a packet at branch
points of its X/Y tree, so a tree's cost is its set of distinct links
(core/noc.py computes this per source with Python loops).  At chip scale
both the setup and the hot path are vectorized:

* **setup** — each source's X/Y multicast tree is derived ARITHMETICALLY
  from its destination coordinate array (one eastward run + one westward
  run on the source row, one vertical run per destination column), so
  building the incidence never walks ``xy_route`` hop by hop.  Trees are
  stored sparse: a CSR ``SparseIncidence`` of (link_ids, source_ptr) —
  O(sum of tree sizes) memory instead of O(P * n_links).
* **per tick** — traffic is either the dense einsum

      link_load[l] = sum_p  packets[p] * incidence[p, l]

  over the densified incidence, or (preferred once trees are sparse
  relative to the mesh) a gather + segment-sum over the CSR entries
  (``repro.kernels.link_load``).  Both paths are exact on integer-valued
  packet counts, so they agree bitwise; ``ChipSim`` auto-selects from the
  incidence shape (mesh size, density, per-link fan-in).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from repro.core.noc import NocSpec, ORIENTATIONS, build_tree
from repro.kernels.event_gather.ops import (EVENT_GATHER_IMPLS,
                                            active_source_set,
                                            event_link_loads)
from repro.kernels.link_load.ops import link_loads_cols, link_loads_csc

SPIKE_PACKET_BITS = 64        # header-only DNoC spike packet (core/noc.py)

# selectable sparse accumulation kernels: the column plan (bucketed
# gathers + prefix adds) or the Pallas sorted-segment prefix-sum kernel;
# "auto" resolves to the column plan (chosen on XLA:CPU timings; which
# path wins on the TPU is not measured yet)
LINK_LOAD_IMPLS = ("auto", "column_plan", "pallas")

# incidence density above which the dense einsum beats the gather +
# segment-sum (small meshes / near-broadcast traffic); ChipSim.run uses it
# to auto-select the accounting path
DENSE_DENSITY = 0.25

# the column plan unrolls one gather+add per column (= max sources sharing
# one link), so fan-in-heavy graphs that pass the density test would still
# trace an O(P)-op tick body; above this column count auto-select falls
# back to the dense einsum
MAX_SPARSE_COLS = 128

# below this mesh size the dense einsum is a trivially small GEMV that
# beats the sparse plan's fixed op overhead (BENCH_pr3.json: the sparse
# path only breaks even around 8x8-QPE / 256-PE meshes), so auto-select
# keeps small chips dense
MIN_SPARSE_LINKS = 128


@dataclass(frozen=True)
class MeshSpec:
    """W x H QPE mesh; PEs number QPE-major (PE p lives in QPE p // 4)."""
    width: int
    height: int
    pes_per_qpe: int = 4

    @property
    def n_qpes(self) -> int:
        return self.width * self.height

    @property
    def n_pes(self) -> int:
        return self.n_qpes * self.pes_per_qpe

    def qpe_coord(self, q: int) -> tuple[int, int]:
        return (q % self.width, q // self.width)

    def pe_coord(self, p: int) -> tuple[int, int]:
        return self.qpe_coord(p // self.pes_per_qpe)

    @staticmethod
    def for_pes(n_pes: int, pes_per_qpe: int = 4) -> "MeshSpec":
        """Smallest near-square mesh holding ``n_pes`` PEs."""
        q = -(-n_pes // pes_per_qpe)
        w = int(np.ceil(np.sqrt(q)))
        h = -(-q // w)
        return MeshSpec(w, h, pes_per_qpe)


def _concat_ranges(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Concatenation of the integer ranges [starts[i], starts[i]+lens[i]),
    without a Python loop."""
    ends = np.cumsum(lens)
    total = int(ends[-1]) if lens.size else 0
    if total == 0:
        return np.empty(0, np.int64)
    return np.repeat(starts, lens) + np.arange(total) - np.repeat(
        ends - lens, lens)


@dataclass
class SparseIncidence:
    """CSR multicast-tree incidence: source p's tree is the distinct link
    ids ``link_ids[source_ptr[p]:source_ptr[p+1]]``.

    Equivalent to the dense 0/1 ``(P, n_links)`` tensor (``dense()``) but
    O(nnz) = O(sum of tree sizes) instead of O(P * n_links) — the per-tree
    link count is O(mesh diameter), not O(n_links), so board-scale meshes
    stay linear.  ``tree_hops[p]`` is the worst hop depth of source p's
    tree (packet latency), computed in the same construction pass.
    """
    link_ids: np.ndarray        # (nnz,) int32 — distinct within a source
    source_ptr: np.ndarray      # (P + 1,) int64 CSR row pointer
    n_links: int
    tree_hops: np.ndarray       # (P,) int32 worst-case hops per source

    @property
    def n_sources(self) -> int:
        return len(self.source_ptr) - 1

    @property
    def nnz(self) -> int:
        return len(self.link_ids)

    @property
    def density(self) -> float:
        cells = self.n_sources * self.n_links
        return self.nnz / cells if cells else 1.0

    @functools.cached_property
    def tree_links(self) -> np.ndarray:
        """(P,) link count of each source's multicast tree
        (== dense().sum(axis=1))."""
        return np.diff(self.source_ptr).astype(np.int64)

    @functools.cached_property
    def src_of_entry(self) -> np.ndarray:
        """(nnz,) source id of each CSR entry — the gather index of the
        per-tick segment-sum."""
        return np.repeat(np.arange(self.n_sources, dtype=np.int32),
                         self.tree_links)

    @staticmethod
    def from_rows(rows, n_links: int, tree_hops) -> "SparseIncidence":
        """Assemble the CSR form from per-source link-id arrays."""
        lens = np.array([r.size for r in rows], np.int64)
        ptr = np.zeros(len(rows) + 1, np.int64)
        np.cumsum(lens, out=ptr[1:])
        ids = (np.concatenate(rows).astype(np.int32) if rows
               else np.empty(0, np.int32))
        return SparseIncidence(link_ids=ids, source_ptr=ptr,
                               n_links=n_links,
                               tree_hops=np.asarray(tree_hops, np.int32))

    @functools.cached_property
    def max_fan_in(self) -> int:
        """Max sources sharing one link == column count of ``col_plan``
        (one vectorized bincount — no sort, no plan build)."""
        return int(np.bincount(self.link_ids, minlength=1).max())

    @functools.cached_property
    def csc(self) -> tuple[np.ndarray, np.ndarray]:
        """Link-major (CSC) view: (src_sorted, link_ptr) with entries
        sorted by link id — the layout of the Pallas prefix-sum kernel."""
        order = np.argsort(self.link_ids, kind="stable")
        counts = np.bincount(self.link_ids, minlength=self.n_links)
        link_ptr = np.zeros(self.n_links + 1, np.int64)
        np.cumsum(counts, out=link_ptr[1:])
        return self.src_of_entry[order], link_ptr

    @functools.cached_property
    def col_plan(self) -> tuple[tuple, np.ndarray]:
        """Prefix-column layout of the per-link segment reduction — the
        engine's per-tick plan.

        Links sorted by source count (heaviest first); column k holds the
        (k+1)-th source id of every link that HAS a (k+1)-th source, so
        the k-th take covers exactly the first ``len(cols[k])`` sorted
        links — per-link loads accumulate as K unrolled 1-D gathers +
        prefix adds (sum of lengths = nnz, no padding, no scatter op),
        then one final take restores link-id order via ``inv_perm``.
        Each link's sum has the same exact integer-valued terms as the
        dense einsum row, so the two agree bitwise.

        Returns (cols, inv_perm): cols a tuple of int32 index arrays of
        non-increasing length, inv_perm (n_links,) int32."""
        src_sorted, link_ptr = self.csc
        counts = np.diff(link_ptr)
        order = np.argsort(-counts, kind="stable")
        inv_perm = np.empty(self.n_links, np.int32)
        inv_perm[order] = np.arange(self.n_links, dtype=np.int32)
        sorted_counts = counts[order]
        cols = []
        for k in range(int(counts.max(initial=0))):
            n_k = int(np.count_nonzero(sorted_counts > k))
            cols.append(src_sorted[link_ptr[order[:n_k]] + k]
                        .astype(np.int32))
        return tuple(cols), inv_perm

    def device_col_plan(self) -> tuple[tuple, "jnp.ndarray"]:
        """``col_plan`` as device arrays, ready to close over in a tick
        loop (hoist ONCE per program, not per tick)."""
        cols, inv_perm = self.col_plan
        return tuple(jnp.asarray(c) for c in cols), jnp.asarray(inv_perm)

    @functools.cached_property
    def padded_rows(self) -> np.ndarray:
        """(P, max tree size) rectangular row layout: source p's link ids
        right-padded with the sentinel ``n_links`` — the gatherable form
        the event engine's compacted-index kernels index by active source
        (``repro.kernels.event_gather``)."""
        L = max(1, int(self.tree_links.max(initial=0)))
        out = np.full((self.n_sources, L), self.n_links, np.int32)
        if self.nnz:
            col = (np.arange(self.nnz)
                   - np.repeat(self.source_ptr[:-1], self.tree_links))
            out[self.src_of_entry, col] = self.link_ids
        return out

    def dense(self) -> np.ndarray:
        """Materialize the (P, n_links) 0/1 incidence tensor."""
        m = np.zeros((self.n_sources, self.n_links), np.float32)
        m[self.src_of_entry, self.link_ids] = 1.0
        return m


class NocAccounting:
    """Per-tick NoC accounting over a CSR/dense multicast incidence.

    Shared by the on-chip ``MeshNoc`` and the board-level
    ``repro.board.BoardNoc``: anything with a ``spec`` (``NocSpec``), an
    ``n_links`` link count and a ``link_load_impl`` knob prices traffic
    the same way, so single-chip and board programs run on one engine.
    All methods are traced inside the engine's scan; none hold state.
    """

    # -- sparse kernel selection ------------------------------------------

    def resolve_link_load_impl(self, impl: str | None = None) -> str:
        """Resolve the sparse accumulation kernel ("auto" -> the column
        plan; "pallas" selects the sorted-segment prefix-sum kernel)."""
        impl = impl or getattr(self, "link_load_impl", "auto")
        if impl not in LINK_LOAD_IMPLS:
            raise ValueError(f"unknown link_load_impl {impl!r}; "
                             f"expected one of {LINK_LOAD_IMPLS}")
        return "column_plan" if impl == "auto" else impl

    def device_plan(self, sinc: "SparseIncidence",
                    impl: str | None = None) -> tuple:
        """Device-resident per-tick plan for ``noc_loads``: a tagged
        layout matching the selected kernel.  Hoist ONCE per program,
        outside the tick closure."""
        impl = self.resolve_link_load_impl(impl)
        if impl == "column_plan":
            return ("column_plan", sinc.device_col_plan())
        src_sorted, link_ptr = sinc.csc
        return ("pallas", (jnp.asarray(src_sorted), jnp.asarray(link_ptr)))

    def noc_loads(self, packets, plan, payload_bits):
        """One tick's (link_loads, flit_loads) through the plan built by
        ``device_plan`` — the engine's sparse hot path, kernel-agnostic.
        Both kernels sum the same exact integer-valued terms per link, so
        every impl agrees bitwise with the dense einsum."""
        kind, data = plan
        if kind == "column_plan":
            cols, inv_perm = data
            return self.noc_loads_sparse(packets, cols, inv_perm,
                                         payload_bits)
        src_sorted, link_ptr = data
        pk = packets.astype(jnp.float32)
        w = pk * self.packet_flits(payload_bits)
        ll = link_loads_csc(pk, src_sorted, link_ptr, n_links=self.n_links)
        fl = link_loads_csc(w, src_sorted, link_ptr, n_links=self.n_links)
        return ll, fl

    # -- event-mode accounting (compacted active-source buffer) ------------

    def resolve_event_impl(self, impl: str | None = None) -> str:
        """Resolve the event-mode accumulation kernel.  "auto" delegates
        to the dense-weight column plan: it is already O(nnz) and
        scatter-free (chosen on XLA:CPU timings) — the compacted-index
        kernels ("gather", "pallas"; ``repro.kernels.event_gather``) are
        the TPU-shaped variants whose work is bounded by the event buffer
        instead of P."""
        impl = impl or getattr(self, "event_impl", "auto")
        if impl not in EVENT_GATHER_IMPLS:
            raise ValueError(f"unknown event_gather impl {impl!r}; "
                             f"expected one of {EVENT_GATHER_IMPLS}")
        return "column_plan" if impl == "auto" else impl

    def event_plan(self, sinc: "SparseIncidence",
                   impl: str | None = None) -> tuple:
        """Device-resident per-tick plan for ``event_noc_loads``.  Hoist
        ONCE per program, outside the tick closure."""
        impl = self.resolve_event_impl(impl)
        if impl == "column_plan":
            return ("column_plan", sinc.device_col_plan())
        return (impl, jnp.asarray(sinc.padded_rows))

    def event_noc_loads(self, packets, plan, payload_bits, idx=None):
        """Event-mode twin of ``noc_loads``: one tick's (link_loads,
        flit_loads).  ``idx`` is an optional pre-compacted active-source
        buffer (sentinel P on unused lanes) — it must cover every source
        with nonzero packets; when None the compaction runs here at full
        width, which is always exact.  Every impl sums the same exact
        integer-valued terms per link, so event and dense accounting
        agree bitwise."""
        kind, data = plan
        if kind == "column_plan":
            return self.noc_loads(packets, plan, payload_bits)
        if idx is None:
            idx, _ = active_source_set(packets, packets.shape[-1])
        w = packets.astype(jnp.float32) * self.packet_flits(payload_bits)
        ll = event_link_loads(idx, packets, data, n_links=self.n_links,
                              impl=kind)
        fl = event_link_loads(idx, w, data, n_links=self.n_links, impl=kind)
        return ll, fl

    def touched_link_counts(self, link_loads) -> dict:
        """Per-tier count of links carrying any traffic this tick — the
        activity telemetry both execution modes record identically
        (``repro.obs`` activity probes)."""
        hit = (link_loads > 0).astype(jnp.float32)
        return {tier: jnp.matmul(hit, jnp.asarray(mask), precision="highest")
                for tier, mask in self.tier_masks().items()}

    # -- per-tick accounting (traced; dense or CSR) -----------------------

    def link_loads(self, packets, inc) -> jnp.ndarray:
        """packets: (..., n_sources) packet counts emitted per source this
        tick; inc: (n_sources, n_links).  Returns (..., n_links) loads.

        Every float32 contraction of the tick runs at HIGHEST precision:
        at the default, a TPU multiplies in bfloat16, which holds
        integers exactly only up to 256 — and flit counts exceed that."""
        return jnp.einsum("...p,pl->...l", packets.astype(jnp.float32),
                          jnp.asarray(inc), precision="highest")

    def link_loads_sparse(self, packets, buckets, inv_perm):
        """Sparse twin of ``link_loads``: bucketed column gathers +
        prefix adds — O(nnz) instead of the dense O(P * n_links), with no
        scatter in the hot path.

        ``buckets``/``inv_perm`` are ``SparseIncidence.col_plan`` (pass
        device index arrays, hoisted out of tick loops).  Bitwise-equal
        to the dense einsum on integer-valued counts."""
        return link_loads_cols(packets.astype(jnp.float32), buckets,
                               inv_perm, n_links=self.n_links)

    def spike_energy_j(self, loads) -> jnp.ndarray:
        """Energy of header-only spike packets from total link traversals."""
        return (loads.sum(axis=-1) * SPIKE_PACKET_BITS
                * self.spec.pj_per_bit_hop * 1e-12)

    # -- typed packet classes (graded payloads over the DNoC) --------------

    def packet_flits(self, payload_bits) -> jnp.ndarray:
        """Flits per packet given per-source payload bits (0 = header-only
        spike packet = 1 flit; graded = ceil(bits / 128) flits)."""
        pb = jnp.asarray(payload_bits)
        return jnp.where(pb > 0, -(-pb // self.spec.payload_bits), 1)

    def packet_bits(self, payload_bits) -> jnp.ndarray:
        """Bits on the wire per link traversal of one packet: 64 b for a
        spike packet, ceil(bits/128) flits of 192 b for graded payloads."""
        pb = jnp.asarray(payload_bits)
        return jnp.where(pb > 0, self.packet_flits(pb) * self.spec.flit_bits,
                         SPIKE_PACKET_BITS)

    def flit_loads(self, packets, inc, payload_bits) -> jnp.ndarray:
        """Per-link flit traffic: each source's packets weighted by its
        packet's flit count before hitting the incidence tensor."""
        w = packets.astype(jnp.float32) * self.packet_flits(payload_bits)
        return jnp.einsum("...p,pl->...l", w, jnp.asarray(inc),
                          precision="highest")

    def flit_loads_sparse(self, packets, buckets, inv_perm, payload_bits):
        """Sparse twin of ``flit_loads`` (same column plan as
        ``link_loads_sparse``)."""
        w = packets.astype(jnp.float32) * self.packet_flits(payload_bits)
        return link_loads_cols(w, buckets, inv_perm, n_links=self.n_links)

    def noc_loads_sparse(self, packets, buckets, inv_perm, payload_bits):
        """One tick's (link_loads, flit_loads) through one fused column
        pass — the column-plan sparse hot path."""
        pk = packets.astype(jnp.float32)
        w = jnp.stack([pk, pk * self.packet_flits(payload_bits)])
        both = link_loads_cols(w, buckets, inv_perm, n_links=self.n_links)
        return both[0], both[1]

    def traffic_energy_j(self, packets, tree_links, payload_bits):
        """Energy of one tick's multicast traffic, packet-class aware.

        packets (..., P) packets emitted per source; tree_links (P,) link
        count of each source's multicast tree (``SparseIncidence.
        tree_links`` == inc.sum(axis=1)); payload_bits (..., P) or (P,).
        Spike packets cost 64 b per link traversal, graded packets cost
        their flit footprint.  Representation-independent: both the dense
        and the sparse engine path call this with the same inputs.
        """
        bits = (packets.astype(jnp.float32)
                * jnp.asarray(tree_links, jnp.float32)
                * self.packet_bits(payload_bits))
        return bits.sum(axis=-1) * self.spec.pj_per_bit_hop * 1e-12

    def congestion(self, loads) -> jnp.ndarray:
        """Peak per-link load (packets / tick) — the SpiNNCer-style traffic
        bottleneck metric."""
        return loads.max(axis=-1)

    def tier_masks(self) -> dict:
        """Named 0/1 masks over the link-id space, one per link tier —
        what the telemetry layer (``repro.obs``) uses to split per-link
        records into per-tier tracks.  A single-chip NoC has one tier;
        the board NoC adds the chip-to-chip SerDes tier."""
        return {"onchip": np.ones(self.n_links, np.float32)}

    def link_capacity_packets(self, t_window_s: float,
                              packet_bits: int = SPIKE_PACKET_BITS) -> float:
        """Packets one link can carry in ``t_window_s`` at the NoC clock."""
        flits = -(-packet_bits // self.spec.payload_bits)
        cycles_per_packet = self.spec.hop_cycles * flits
        return t_window_s * self.spec.freq_hz / cycles_per_packet

    def hop_latency_s(self, n_hops) -> float:
        return n_hops * self.spec.hop_cycles / self.spec.freq_hz


@dataclass
class MeshNoc(NocAccounting):
    """Link enumeration + incidence construction + vectorized accounting."""
    mesh: MeshSpec
    spec: NocSpec = field(default_factory=NocSpec)
    link_load_impl: str = "auto"       # sparse kernel: see LINK_LOAD_IMPLS

    def __post_init__(self):
        links = []
        for y in range(self.mesh.height):
            for x in range(self.mesh.width):
                if x + 1 < self.mesh.width:
                    links.append(((x, y), (x + 1, y)))
                    links.append(((x + 1, y), (x, y)))
                if y + 1 < self.mesh.height:
                    links.append(((x, y), (x, y + 1)))
                    links.append(((x, y + 1), (x, y)))
        self.links = links
        self.link_index = {lk: i for i, lk in enumerate(links)}
        # arithmetic link-id tables, keyed by the link's lower endpoint —
        # what lets tree construction index whole runs of links at once
        W, H = self.mesh.width, self.mesh.height
        self._id_e = np.full((W, H), -1, np.int32)   # (x,y) -> (x+1,y)
        self._id_w = np.full((W, H), -1, np.int32)   # (x+1,y) -> (x,y)
        self._id_n = np.full((W, H), -1, np.int32)   # (x,y) -> (x,y+1)
        self._id_s = np.full((W, H), -1, np.int32)   # (x,y+1) -> (x,y)
        for i, ((x0, y0), (x1, y1)) in enumerate(links):
            if x1 == x0 + 1:
                self._id_e[x0, y0] = i
            elif x1 == x0 - 1:
                self._id_w[x1, y1] = i
            elif y1 == y0 + 1:
                self._id_n[x0, y0] = i
            else:
                self._id_s[x0, y1] = i

    @property
    def n_links(self) -> int:
        return len(self.links)

    @property
    def n_onchip_links(self) -> int:
        """Every link of a single-chip mesh is on-chip — the shared
        tier-boundary accessor the benchmark link profiles use (the
        board NoC's first ``n_onchip_links`` ids are its on-chip tier)."""
        return len(self.links)

    # -- incidence construction (setup time, numpy) -----------------------

    def tree_links(self, src: tuple, dsts, orientation: str = "xy") -> set:
        """Distinct links of the dimension-ordered multicast tree
        src -> dsts (shared prefixes paid once — the router duplicates at
        branch points).

        Reference implementation: the shared ``repro.core.noc.build_tree``
        walk.  The vectorized ``tree_link_ids`` is validated against it
        in tests."""
        return set(build_tree(src, dsts, orientation))

    def tree_link_ids(self, src, dst_xy: np.ndarray,
                      orientation: str = "xy") -> np.ndarray:
        """Distinct link ids of the dimension-ordered multicast tree
        src -> dst coords, derived arithmetically from the destination
        coordinate array.

        Trunk-first routing makes the tree one trunk through the source
        (along the first-routed dimension, out to the farthest
        destination on either side) plus, per destination lane, one
        perpendicular run to the farthest destination — no
        per-destination route walk.  ``orientation`` picks the trunk
        dimension: "xy" (X first, the historical default) or "yx" — the
        latter is the same arithmetic over the transposed link-id
        tables, so both orientations share ONE implementation.
        """
        d = np.asarray(dst_xy, np.int64).reshape(-1, 2)
        if not d.size:
            return np.empty(0, np.int32)
        if orientation == "yx":
            # transposed space: u = y, v = x; +u links are north, +v east
            return self._oriented_tree_ids(
                (int(src[1]), int(src[0])), d[:, ::-1],
                self._id_n.T, self._id_s.T, self._id_e.T, self._id_w.T,
                self.mesh.height)
        if orientation != "xy":
            raise ValueError(f"unknown orientation {orientation!r}; "
                             f"expected one of {ORIENTATIONS}")
        return self._oriented_tree_ids(
            (int(src[0]), int(src[1])), d,
            self._id_e, self._id_w, self._id_n, self._id_s,
            self.mesh.width)

    def _oriented_tree_ids(self, src, d, id_pos, id_neg, id_up, id_dn,
                           width) -> np.ndarray:
        """Trunk + branch-run construction in an orientation-agnostic
        frame: (u, v) coordinates where u is the trunk dimension, with
        ``id_pos``/``id_neg`` the +u/-u link tables, ``id_up``/``id_dn``
        the +v/-v tables (transposed views for "yx") and ``width`` the
        u-extent of the mesh."""
        su, sv = src
        du, dv = d[:, 0], d[:, 1]
        parts = []
        umax, umin = int(du.max()), int(du.min())
        if umax > su:
            parts.append(id_pos[su:umax, sv])
        if umin < su:
            parts.append(id_neg[umin:su, sv])
        up = dv > sv
        if up.any():
            top = np.full(width, sv, np.int64)
            np.maximum.at(top, du[up], dv[up])
            cols = np.flatnonzero(top > sv)
            lens = top[cols] - sv
            vs = _concat_ranges(np.full(cols.size, sv, np.int64), lens)
            parts.append(id_up[np.repeat(cols, lens), vs])
        dn = dv < sv
        if dn.any():
            bot = np.full(width, sv, np.int64)
            np.minimum.at(bot, du[dn], dv[dn])
            cols = np.flatnonzero(bot < sv)
            lens = sv - bot[cols]
            vs = _concat_ranges(bot[cols], lens)
            parts.append(id_dn[np.repeat(cols, lens), vs])
        if not parts:
            return np.empty(0, np.int32)
        return np.concatenate(parts).astype(np.int32)

    def sparse_incidence(self, src_coords, dst_coord_lists,
                         orientations=None) -> SparseIncidence:
        """CSR incidence + per-source tree hop depths in one pass.

        ``dst_coord_lists[i]`` is source i's destination coordinate array
        (anything ``np.asarray`` can shape to (n, 2); duplicates and the
        source's own coordinate are harmless).  ``orientations`` is an
        optional per-source sequence of tree orientations ("xy"/"yx");
        None keeps every tree X-first — bit-identical to the
        pre-orientation compiler."""
        src = np.asarray(src_coords, np.int64).reshape(-1, 2)
        rows = []
        hops = np.zeros(len(src), np.int32)
        for i, (s, d) in enumerate(zip(src, dst_coord_lists)):
            d = np.asarray(d, np.int64).reshape(-1, 2)
            o = orientations[i] if orientations is not None else "xy"
            rows.append(self.tree_link_ids(s, d, orientation=o))
            if d.size:
                hops[i] = int(np.abs(d - s).sum(axis=1).max())
        return SparseIncidence.from_rows(rows, self.n_links, hops)

    def incidence_row(self, src: tuple, dsts) -> np.ndarray:
        row = np.zeros(self.n_links, np.float32)
        row[self.tree_link_ids(src, np.asarray(list(dsts),
                                               np.int64).reshape(-1, 2))] = 1.0
        return row

    def incidence(self, src_coords, dst_coord_lists) -> np.ndarray:
        """(n_sources, n_links) 0/1 multicast-tree incidence tensor."""
        return self.sparse_incidence(src_coords, dst_coord_lists).dense()

    def tree_hops(self, src: tuple, dsts) -> int:
        """Worst-case hop depth of the multicast tree (packet latency)."""
        return max((abs(src[0] - d[0]) + abs(src[1] - d[1]) for d in dsts),
                   default=0)
