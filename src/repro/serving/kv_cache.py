"""Tiered paged KV cache: device pools + host tiers + page tables.

The KV cache is the serving analogue of the paper's application heap:

  placement levels for a KV page (region):
    1 = warm  device pool, int8-HBM   (C5/C6-class tier: low latency)
    2 = cold  device pool, int4-HBM   (C9-class: denser, mid latency)
    3 = host  int8 behind PCIe        (C7-class)
    4 = host  int4 behind PCIe        (C10/C12-class: best TCO)

  Storage is codec-class-major: device payloads live in one shared buffer
  per codec class (``c8_*`` int8, ``c4_*`` int4) and page tables hold GLOBAL
  class-buffer rows, so N pools of the same class need zero per-step payload
  concatenation and same-class migrations are pure table edits
  (``exchange_slots`` moves row ownership, not bytes). Each pool's
  ``SlotAllocator`` starts with a contiguous row range of its class
  partition (``ClassPartition``); exchanges interleave the ranges over time.

  The dense *recent window* plays DRAM's role for the newest tokens and is
  hotness-exempt (always uncompressed). Pages in device pools are read by
  every decode step through the paged-attention kernel, which returns exact
  per-page softmax mass — the hotness telemetry. Host pages are not read
  in-step (the access-skip is the "fault cost": quality + swap latency);
  the manager re-promotes them on waterfall/analytical recommendation and
  the engine swaps payloads through the warm pool. Host pages DO appear to
  the decode step as *sentinel rows*: a per-page key centroid in
  ``state.host_summary`` plus ``host_table``/``host_n``, which the fused
  attention launch scores into a "would-have-touched" softmax mass — the
  in-engine hotness signal that feeds the prefetch predictor directly
  (``manager.record_host_mass``) without ever fetching a payload or
  perturbing placement-driving telemetry.

All placement state is host-side numpy (daemon side). Two placement vectors
exist on purpose:

  * ``manager.placement`` — the policy's *desired* placement (what the
    TierScape model computed at the window boundary),
  * ``self.physical``     — where each page's payload *actually* lives.

``migrate_batch`` reconciles the two: it groups the migration plan into
(src, dst) cohorts, gathers each cohort's pages into one [P, T, KV, hd]
batch, and executes the cohort with a single fused ``transcode_pages``
kernel dispatch (or a raw media copy on the same-codec fast path) — turning
the per-window migration cost from O(pages) kernel dispatches into
O(cohorts). The legacy per-page ``migrate`` path is kept as the equivalence
oracle and for single-page evictions.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import tco
from repro.core.manager import ManagerConfig, TierScapeManager
from repro.core.pools import ClassPartition, SlotAllocator, exchange_slots
from repro.core.tiers import TierSet, get as get_tier
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.media.devices import adaptive_devices, make_queues
from repro.media.pipeline import MigrationPipeline
from repro.media.ringbuf import PinnedRing
from repro.runtime.serve import CLASS_FIELDS, TieredKVState, init_tiered_kv_state
from repro.serving.spans import SpanRecorder

# Placement indices (0 stays "uncompressed DRAM" for cost-model parity with
# the paper; KV pages never occupy it — the recent window does).
WARM, COLD, HOST8, HOST4 = 1, 2, 3, 4
KV_TIER_IDS = ("C5", "C9", "C7", "C10")  # default: int8-HBM, int4-HBM, int8-host, int4-host
# Codec widths of the default pool split; instance widths live in
# ``self._bits`` (``pool_bits`` can make both device pools share a codec
# class, in which case they share one class buffer).
_BITS = {WARM: 8, COLD: 4, HOST8: 8, HOST4: 4}
_DEVICE = (WARM, COLD)
_POOL = {WARM: "warm", COLD: "cold"}
# Characterized tier ids by (pool, codec width) for the device pools.
_DEVICE_TIER_IDS = {
    ("warm", 8): "C5",  # SL-I8-HB
    ("warm", 4): "C8",  # SL-I4-HB
    ("cold", 8): "C6",  # PK-I8-HB
    ("cold", 4): "C9",  # PK-I4-HB
}
# A page staged out of its source tier but not yet committed to its
# destination by the async migration pipeline. Every placement mask in this
# module is a positive-level comparison, so in-flight pages drop out of
# telemetry folds, eviction scans and capacity pre-passes automatically.
INFLIGHT = -1


def kv_tierset(
    page_elems: int,
    warm_bits: int = 8,
    cold_bits: int = 4,
    host_device: str = "",
) -> TierSet:
    """TierSet for a device-pool codec split. Defaults reproduce
    ``KV_TIER_IDS``; same-width splits (e.g. warm_bits=cold_bits=8) pick the
    matching characterized tiers so byte/latency accounting follows the
    deployed codecs. ``host_device`` rebinds the two host tiers onto another
    media device from the catalog (e.g. ``"cxl_hw"`` for the
    hardware-compressed CXL expander) without changing their codec/pool
    identity — payload layout and migration semantics stay byte-identical;
    only media billing and service times move."""
    ids = (
        _DEVICE_TIER_IDS[("warm", int(warm_bits))],
        _DEVICE_TIER_IDS[("cold", int(cold_bits))],
        "C7",
        "C10",
    )
    ts = tuple(get_tier(t) for t in ids)
    if host_device:
        ts = ts[:2] + tuple(
            dataclasses.replace(t, media_device=host_device) for t in ts[2:]
        )
    return TierSet(tiers=ts, block_elems=page_elems)


@dataclasses.dataclass
class PageMeta:
    layer: int
    seq_slot: int
    page_idx: int  # logical page index within the sequence
    pool_slot: int = -1  # slot within its current pool


@dataclasses.dataclass
class ParkedPage:
    """One preempted page lifted out of the region space: the exact stored
    host-tier bytes plus where to land it on resume."""

    layer: int
    page: int  # logical page index within the sequence
    host_level: int  # HOST8 | HOST4 — codec of the parked payload
    restore_level: int  # pre-preemption placement to swap back to on resume
    payload: Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]


@dataclasses.dataclass
class ParkedSlot:
    """A preempted batch slot's full KV state, detached from the cache.

    ``park_slot`` produces one after ``demote_slot_to_host`` has pushed every
    device-resident page to its same-codec host tier (a raw media copy, no
    transcode — so payload bytes round-trip bit-exactly). The parked request
    can later be restored into ANY free slot of ANY engine with the same
    geometry via ``restore_slot``; pages re-enter through the normal swap-in
    cohort machinery, billed like every other promotion."""

    tenant: int
    pages: List[ParkedPage]
    recent_k: np.ndarray  # [L, R, KV, hd] — slot row of the recent window
    recent_v: np.ndarray
    recent_len: int
    total_len: int


class _TableEditor:
    """Batched host-side edits of the device page tables.

    All table mutations of one migrate/append batch happen on numpy copies;
    ``commit`` writes each table back to the device exactly once, instead of
    one ``.at[].set`` dispatch per page. Covers the host sentinel table too
    (it has the same row layout as the device pool tables)."""

    _POOLS = ("warm", "cold", "host")

    def __init__(self, state: TieredKVState):
        self.tables = {p: np.array(getattr(state, f"{p}_table")) for p in self._POOLS}
        self.counts = {p: np.array(getattr(state, f"{p}_n")) for p in self._POOLS}

    def remove(self, pool: str, layers, slots, pool_slots) -> None:
        t, c = self.tables[pool], self.counts[pool]
        for la, sl, ps in zip(layers, slots, pool_slots):
            n = int(c[la, sl])
            row = t[la, sl]
            idx = int(np.where(row[:n] == ps)[0][0])
            row[idx] = row[n - 1]
            row[n - 1] = 0
            c[la, sl] = n - 1

    def insert(self, pool: str, layers, slots, pool_slots) -> None:
        t, c = self.tables[pool], self.counts[pool]
        for la, sl, ps in zip(layers, slots, pool_slots):
            n = int(c[la, sl])
            t[la, sl, n] = ps
            c[la, sl] = n + 1

    def commit(self, state: TieredKVState) -> TieredKVState:
        kw = {}
        for p in self._POOLS:
            kw[f"{p}_table"] = jnp.asarray(self.tables[p])
            kw[f"{p}_n"] = jnp.asarray(self.counts[p])
        return dataclasses.replace(state, **kw)


class TieredKVCache:
    """Host-side controller for one attention-layer-group x batch of slots."""

    def __init__(
        self,
        cfg: ModelConfig,
        n_attn_layers: int,
        batch_slots: int,
        page_tokens: int,
        max_seq_len: int,
        recent_window: int,
        manager_cfg: ManagerConfig,
        warm_frac: float = 0.5,
        tenant_quota: Optional[Dict[str, Dict[int, int]]] = None,
        async_migration: bool = False,
        ring_slots: int = 64,
        media_step_s: float = 50e-6,
        prefetch: bool = False,
        prefetch_max_pages: int = 8,
        pool_bits: Optional[Dict[str, int]] = None,
        host_media_device: str = "",
        fault_plan=None,
    ):
        """``tenant_quota`` maps pool name ("warm"/"cold") -> {tenant id ->
        max concurrently held slots}. When a pool carries a quota, every
        tenant that allocates from it must appear in the dict (the
        ``SlotAllocator`` hard contract) — quota exhaustion spills that
        tenant's pages down-tier instead of letting it drain the shared
        free list. ``async_migration`` routes window migration plans
        through the double-buffered media pipeline instead of the blocking
        ``migrate_batch`` path. ``prefetch`` (async-only) speculatively
        stages warming host pages through the ring's reserved slice so a
        boundary promotion commits without paying the swap-in read;
        placements stay bit-identical to a prefetch-free run. ``pool_bits``
        maps pool name -> codec width (8 or 4) for the device pools,
        default ``{"warm": 8, "cold": 4}``; pools of the same width share
        one codec-class buffer and same-class migrations move no payload
        bytes. ``host_media_device`` rebinds the two host tiers onto a
        different media-catalog device (e.g. ``"cxl_hw"``): payload layout
        is untouched, but host-page traffic is billed/serviced on that
        device, and adaptive devices get fed real encoded sizes at every
        window boundary. ``fault_plan`` (a ``media.faults.FaultPlan``) arms
        deterministic fault injection: every media queue's device is wrapped
        in a ``FaultyMediaDevice`` sharing one window clock advanced at
        ``end_window``, down devices quarantine their tiers and defer their
        cohorts, and staged payloads carry end-to-end CRCs."""
        self.cfg = cfg
        self.la = n_attn_layers
        self.bs = batch_slots
        self.pt = page_tokens
        self.max_pages = max_seq_len // page_tokens
        self.recent_window = recent_window
        hd = cfg.head_dim_()
        kv = cfg.n_kv_heads
        self.page_elems = page_tokens * kv * hd * 2  # K and V
        total_pages = self.la * self.bs * self.max_pages
        warm_cap = max(int(total_pages * warm_frac), 8)
        cold_cap = max(total_pages, 8)

        # Codec-class-major storage: each device pool is a codec width over a
        # shared class buffer. ``self._cls[pool]`` names the class fields the
        # pool's pages live in; ``self._bits[level]`` the codec width per
        # placement level. The default (8, 4) split keeps both pools alone in
        # their class, so buffers, row numbering and allocation order are
        # identical to the pre-class-major layout.
        pool_bits = dict(pool_bits or {})
        wb = int(pool_bits.get("warm", 8))
        cb = int(pool_bits.get("cold", 4))
        if wb not in (8, 4) or cb not in (8, 4):
            raise ValueError(f"pool_bits must be 8 or 4, got warm={wb} cold={cb}")
        self._pool_bits = {"warm": wb, "cold": cb}
        self._cls = {"warm": "c8" if wb == 8 else "c4", "cold": "c8" if cb == 8 else "c4"}
        self._bits = {WARM: wb, COLD: cb, HOST8: 8, HOST4: 4}
        part = ClassPartition([("warm", wb, warm_cap), ("cold", cb, cold_cap)])

        self.state = init_tiered_kv_state(
            cfg,
            batch_slots,
            page_tokens=page_tokens,
            warm_pages=warm_cap,
            cold_pages=cold_cap,
            max_pages_per_seq=self.max_pages,
            recent_window=recent_window,
            n_attn_layers=n_attn_layers,
            host_slots=self.bs * self.max_pages,
            warm_bits=wb,
            cold_bits=cb,
        )
        # Host tier pools: dict slot -> (k_pay, k_sc, v_pay, v_sc) numpy.
        self.host_pages: Dict[int, Tuple[np.ndarray, ...]] = {}

        # Region space: (layer, slot, page) flattened.
        self.n_regions = total_pages
        self.host_media_device = str(host_media_device)
        self.manager = TierScapeManager(
            kv_tierset(self.page_elems, wb, cb, host_device=self.host_media_device),
            self.n_regions,
            region_bytes=self.page_elems * 2,
            cfg=manager_cfg,
        )
        # KV pages never sit in DRAM; block the option by pricing it out.
        self._page_exists = np.zeros(self.n_regions, bool)
        # Where the payload actually lives (manager.placement is the desired
        # placement the policy computed; the executor reconciles them).
        self.physical = np.zeros(self.n_regions, np.int64)
        # Device-pool slot management. SlotAllocators (daemon side) own the
        # free lists; ``tenant_quota`` caps per-tenant residency so one
        # tenant cannot exhaust a shared pool (the MaxMem failure mode).
        # Slots are GLOBAL class-buffer rows: each pool's allocator starts
        # with its contiguous ``ClassPartition`` range (base offset); with
        # the default split both bases are 0, reproducing per-pool numbering.
        tenant_quota = tenant_quota or {}
        self._alloc = {
            "warm": SlotAllocator(warm_cap, tenant_quota.get("warm"), base=part.base("warm")),
            "cold": SlotAllocator(cold_cap, tenant_quota.get("cold"), base=part.base("cold")),
        }
        # Host sentinel summary slots (device-side key centroids for the
        # fused kernel's would-have-touched rows): PER-LAYER free lists —
        # a layer can host at most bs*max_pages pages, so per-layer sizing
        # keeps ``host_summary`` at [L, bs*max_pages, ...] instead of
        # replicating the global slot space per layer. Allocation can
        # never fail.
        self._host_alloc = [
            SlotAllocator(self.bs * self.max_pages) for _ in range(self.la)
        ]
        self._pool_slot = np.full(self.n_regions, -1, np.int64)
        # Summary slot of each host-resident page (-1 = no sentinel).
        self._host_slot = np.full(self.n_regions, -1, np.int64)
        # Multi-tenancy: each batch slot is owned by one tenant; a page's
        # tenant is its slot's tenant (pages are keyed by (layer, slot, page),
        # so slot ownership is the isolation boundary).
        self.slot_tenant = np.zeros(self.bs, np.int64)
        self._rid_slot = (np.arange(self.n_regions) // self.max_pages) % self.bs
        self.quality_skipped_mass = 0.0  # cumulative mass of host-excluded pages
        # Compute-kernel dispatch accounting for the migration/ingestion path
        # (quant / dequant / transcode launches — the daemon-tax proxy).
        self.kernel_dispatches = 0
        # Decode-side attention launch accounting: ``record_telemetry`` is
        # called once per decode step and bills the step's actual launch
        # structure via ``kops.decode_launches_per_step`` — 1 launch/layer on
        # the fused path regardless of tier count, O(tiers) on the per-pool
        # oracle — so WindowStats/TCO reports stop billing O(tiers) launches
        # once fusion is on.
        self.attn_launches = 0
        # The same step's attention walk, from host-side page counts: page
        # slots the kernel's grid visits and pages it attends (device-pool
        # pages plus host sentinels) — walked/held is the walk's overhead.
        # A rid's (layer, slot) row is ``rid // max_pages``.
        self.attn_walk_slots = 0
        self.attn_pages = 0
        self.decode_steps_recorded = 0
        # Program spans (``serving/spans.py``); an engine shares this one.
        self.spans = SpanRecorder()

        # --- backing-media subsystem -----------------------------------
        # One MediaQueue per distinct device (shared-bandwidth accounting),
        # a pinned staging ring sized for the fattest page representation
        # (int8 payload + f32 scales, K and V), and the async migration
        # pipeline. serial=True (async_migration off) keeps the blocking
        # window-boundary semantics as the equivalence oracle.
        ts = self.manager.tierset
        self._dev_names = [d.name for d in ts.media_devices()]
        self._page_stored_bytes = np.array(
            [self.page_elems * 2]
            + [t.stored_bytes(self.page_elems, 2) for t in ts.tiers],
            np.int64,
        )
        hd8 = page_tokens * kv * hd  # int8 payload bytes per K (or V) page
        sc = 4 * page_tokens * kv  # f32 scale bytes per K (or V) page
        self.staging_ring = PinnedRing(max(ring_slots, 2), 2 * (hd8 + sc))
        self.media_queues = make_queues(self._dev_names)
        # Deterministic fault injection: wrap every queue's device so the
        # whole substrate shares one fault window clock (advanced at
        # ``end_window`` — the same boundary in serial and async replay).
        self.fault_plan = fault_plan
        self._fault_window = 0
        self._down_devices: set = set()
        self.fault_deferred_pages = 0  # plan entries deferred off down devices
        self.fault_spill_redirects = 0  # commit spills rerouted off down devices
        self._spill_depth = 0  # nested commit-spill depth (redirect guard)
        self._fault_counter_snapshot = (0, 0, 0)
        if fault_plan is not None:
            from repro.media.faults import FaultyMediaDevice

            for q in self.media_queues.values():
                q.device = FaultyMediaDevice(q.device, fault_plan)
        self.async_migration = async_migration
        self.pipeline = MigrationPipeline(
            self, self.staging_ring, self.media_queues,
            step_period_s=media_step_s, serial=not async_migration,
        )
        self._pending_reconcile: List[np.ndarray] = []
        self._media_busy_snapshot: Dict[str, float] = {}
        # Speculative prefetch: only meaningful on the async path (there are
        # no mid-window decode steps to hide the swap-in read behind in
        # serial mode). At most one cohort emission per profile window.
        self.prefetch_enabled = bool(prefetch and async_migration)
        self.prefetch_max_pages = prefetch_max_pages
        self._prefetch_window_emitted = False

    # ------------------------------------------------------------- helpers
    def rid(self, layer: int, slot: int, page: int) -> int:
        return (layer * self.bs + slot) * self.max_pages + page

    def rid_coords(self, rid: int) -> Tuple[int, int, int]:
        layer = rid // (self.bs * self.max_pages)
        slot = (rid // self.max_pages) % self.bs
        page = rid % self.max_pages
        return layer, slot, page

    # ---------------------------------------------------------- multi-tenant
    def set_slot_tenant(self, slot: int, tenant: int) -> None:
        """Tag a batch slot (and all pages it will hold) with a tenant id."""
        self.slot_tenant[slot] = tenant

    def tenant_mask(self, tenant: int) -> np.ndarray:
        """(n_regions,) bool: regions owned by ``tenant`` via their slot."""
        return self.slot_tenant[self._rid_slot] == tenant

    # ------------------------------------------------- pool slot accounting
    # The raw free lists stay visible (tests and tools introspect them), but
    # every mutation goes through the SlotAllocators so per-tenant quota
    # accounting can never drift from the lists.
    @property
    def _free_warm(self) -> List[int]:
        return self._alloc["warm"]._free

    @property
    def _free_cold(self) -> List[int]:
        return self._alloc["cold"]._free

    def _tenant_of_rid(self, rid: int) -> int:
        return int(self.slot_tenant[int(self._rid_slot[rid])])

    def _quota_headroom(self, pool: str, tenant: int) -> int:
        """Slots ``tenant`` may still claim under its quota alone (ignores
        the global free list — the capacity pre-passes handle that)."""
        a = self._alloc[pool]
        if a.tenant_quota is None:
            return a.capacity
        if tenant not in a.tenant_quota:
            raise KeyError(
                f"tenant {tenant!r} allocates from quota'd pool {pool!r} "
                f"but has no quota entry"
            )
        return max(a.tenant_quota[tenant] - a.used_by(tenant), 0)

    def _pool_headroom(self, pool: str, tenant: Optional[int] = None) -> int:
        """Slots allocatable right now: global free list, clipped by the
        tenant's quota when the pool is quota-managed."""
        a = self._alloc[pool]
        free = len(a._free)
        if a.tenant_quota is None or tenant is None:
            return free
        return min(free, self._quota_headroom(pool, tenant))

    def _alloc_slot(self, pool: str, rid: int) -> int:
        a = self._alloc[pool]
        tenant = self._tenant_of_rid(rid) if a.tenant_quota is not None else None
        return a.alloc(int(rid), tenant)

    def _free_slot(self, pool: str, pool_slot: int) -> None:
        self._alloc[pool].free(int(pool_slot))

    # ------------------------------------------------ class-major addressing
    def _same_class(self, src: int, dst: int) -> bool:
        """Device->device move within one codec class: payload bytes stay in
        place in the shared class buffer; only row ownership moves."""
        return src in _DEVICE and dst in _DEVICE and self._bits[src] == self._bits[dst]

    def _gather_rows(self, pool: str, ps):
        """Gather a pool cohort's payload/scale rows from its class buffer
        (rows are global across layers: one row holds one layer's page)."""
        st = self.state
        cls = self._cls[pool]
        return tuple(getattr(st, f"{cls}_{f}")[ps] for f in CLASS_FIELDS)

    def _scatter_rows(self, pool: str, ps, k_pay, k_sc, v_pay, v_sc) -> None:
        st = self.state
        cls = self._cls[pool]
        new = (k_pay, k_sc, v_pay, v_sc)
        self.state = dataclasses.replace(st, **{
            f"{cls}_{f}": getattr(st, f"{cls}_{f}").at[ps].set(x)
            for f, x in zip(CLASS_FIELDS, new)
        })

    def _exchange_rows(self, src: int, dst: int, rids, ps) -> None:
        """Transfer class-row ownership for a same-class cohort: each page's
        row leaves the src allocator and joins the dst allocator (which
        donates a free row back), enforcing dst tenant quota like alloc.
        ``_pool_slot`` is untouched — the rows are global, the page stays
        physically where it is."""
        sa, da = self._alloc[_POOL[src]], self._alloc[_POOL[dst]]
        for r, x in zip(rids, ps):
            tenant = (
                self._tenant_of_rid(int(r)) if da.tenant_quota is not None else None
            )
            exchange_slots(sa, da, int(x), int(r), tenant)

    def _quant_page(self, kpage, vpage, bits: int):
        self.kernel_dispatches += 2
        kp, ks = kref.quant_kv_page(kpage, bits)
        vp, vs = kref.quant_kv_page(vpage, bits)
        return kp, ks, vp, vs

    def _set_placement(self, rids, level) -> None:
        self.physical[rids] = level
        self.manager.placement[rids] = level

    def _invalidate_prefetch(self, rids) -> None:
        """A host page moved or was freed out from under its speculative
        shadow copy: the staged bytes are stale and must never be claimed
        (rids get recycled). Ring credits return; counts as cancelled."""
        if self.prefetch_enabled:
            self.pipeline.discard_speculative(rids, cancelled=True)

    # ------------------------------------------------- host sentinel rows
    # Every page living on a host tier carries a sentinel: its key centroid
    # (mean over the page's T tokens of the dequantized stored K payload —
    # deterministic from the stored bytes) in ``state.host_summary`` plus a
    # ``host_table`` row entry. The fused attention launch scores sentinels
    # for would-have-touched mass without fetching any payload.
    def _host_sentinel_insert(
        self, rids, layers, slots, k_pay, k_sc, bits: int,
        editor: Optional[_TableEditor] = None,
    ) -> None:
        rids = np.asarray(rids, np.int64)
        if rids.size == 0:
            return
        # One dequant dispatch to derive the centroids (daemon-tax billed
        # like every other quant/dequant on the migration path).
        self.kernel_dispatches += 1
        summ = np.asarray(
            kref.dequant_kv_page(jnp.asarray(k_pay), jnp.asarray(k_sc), bits)
        ).mean(axis=1)  # [P, KV, hd]
        hs = np.array(
            [self._host_alloc[int(la)].alloc(int(r)) for la, r in zip(layers, rids)],
            np.int64,
        )
        st = self.state
        self.state = dataclasses.replace(
            st, host_summary=st.host_summary.at[layers, hs].set(jnp.asarray(summ))
        )
        own = editor is None
        editor = editor or _TableEditor(self.state)
        editor.insert("host", layers, slots, hs)
        if own:
            self.state = editor.commit(self.state)
        self._host_slot[rids] = hs

    def _host_sentinel_remove(
        self, rids, layers, slots, editor: Optional[_TableEditor] = None
    ) -> None:
        rids = np.asarray(rids, np.int64)
        if rids.size == 0:
            return
        hs = self._host_slot[rids]
        own = editor is None
        editor = editor or _TableEditor(self.state)
        editor.remove("host", layers, slots, hs)
        if own:
            self.state = editor.commit(self.state)
        for la, x in zip(layers, hs):
            self._host_alloc[int(la)].free(int(x))
        self._host_slot[rids] = -1

    # -------------------------------------------------- page ingestion path
    def append_page(self, layer: int, slot: int, page: int, kpage, vpage) -> None:
        """Single-page ingestion (the batched path is ``append_pages``).
        New page exits the recent window -> warm tier (T1-first, like the
        paper's waterfall: everything starts in the low-latency tier). Falls
        through to the cold tier under warm-pool pressure with nothing left
        to demote (all warm slots held by in-flight migrations)."""
        rid = self.rid(layer, slot, page)
        tenant = self._tenant_of_rid(rid)
        if self._pool_headroom("warm", tenant) == 0:
            # Under a pure quota shortage only this tenant's own warm pages
            # free quota; under global pressure any warm page will do.
            scoped = tenant if self._quota_headroom("warm", tenant) == 0 else None
            self._evict_coldest_warm(tenant=scoped)
        if self._pool_headroom("warm", tenant) == 0:
            self._page_exists[rid] = True
            self._insert(rid, layer, slot, page, kpage, vpage, COLD)
            return
        ps = self._alloc_slot("warm", rid)
        kp, ks, vp, vs = self._quant_page(kpage, vpage, self._bits[WARM])
        self._scatter_rows("warm", ps, kp, ks, vp, vs)
        st = self.state
        n = int(st.warm_n[layer, slot])
        self.state = dataclasses.replace(
            st,
            warm_table=st.warm_table.at[layer, slot, n].set(ps),
            warm_n=st.warm_n.at[layer, slot].set(n + 1),
        )
        self._set_placement(rid, WARM)
        self._page_exists[rid] = True
        self._pool_slot[rid] = ps
        # Live compressibility feedback (paper: measured ratios drive the
        # analytical model).
        self.manager.update_measured_ratio(WARM, 2.0 * kp.size / (kp.size + 4 * ks.size) * 1.0)

    def append_pages(self, entries: Sequence[Tuple[int, int, int]], kpages, vpages) -> None:
        """Batched ingestion: quantize all N new pages with one kernel
        dispatch per destination tier (K and V stacked into one batch) and
        commit the page tables once. ``entries`` is [(layer, slot, page)];
        kpages/vpages are [N, T, KV, hd] float."""
        n = len(entries)
        if n == 0:
            return
        rids = np.array([self.rid(*e) for e in entries], np.int64)
        layers = np.array([e[0] for e in entries], np.int64)
        slots = np.array([e[1] for e in entries], np.int64)
        tenants = self.slot_tenant[slots]

        deficit = n - len(self._free_warm)
        if deficit > 0:
            # Warm pressure: demote the coldest existing warm pages, batched.
            hot = self.manager.telemetry.averaged_hotness(2)
            cand = np.where((self.physical == WARM) & self._page_exists)[0]
            take = cand[np.argsort(hot[cand])][:deficit]
            if take.size:
                self.migrate_batch(take, np.full(take.size, COLD, np.int64))
        if self._alloc["warm"].tenant_quota is not None:
            # Per-tenant pressure: a tenant at quota frees headroom only by
            # demoting its OWN coldest warm pages.
            hot = self.manager.telemetry.averaged_hotness(2)
            for t in np.unique(tenants):
                want = int((tenants == t).sum())
                q_deficit = want - self._quota_headroom("warm", int(t))
                if q_deficit <= 0:
                    continue
                cand = np.where(
                    (self.physical == WARM) & self._page_exists & self.tenant_mask(int(t))
                )[0]
                take = cand[np.argsort(hot[cand])][:q_deficit]
                if take.size:
                    self.migrate_batch(take, np.full(take.size, COLD, np.int64))

        # Per-entry destination: warm while global + tenant headroom lasts,
        # then cold, then (cold quota exhausted) the int4 host tier. With no
        # quotas this degenerates to the first-N-warm split.
        dst_of = np.full(n, HOST4, np.int64)
        warm_fit = self._claim_fits("warm", rids)
        dst_of[warm_fit] = WARM
        rest = np.where(~warm_fit)[0]
        if rest.size:
            cold_fit = self._claim_fits("cold", rids[rest])
            dst_of[rest[cold_fit]] = COLD

        editor = _TableEditor(self.state)
        for dst in (WARM, COLD, HOST4):
            sel = np.where(dst_of == dst)[0]
            if sel.size == 0:
                continue
            p = sel.size
            bits = self._bits[dst]
            pay, sc = kops.quant_pages(jnp.concatenate([kpages[sel], vpages[sel]]), bits)
            self.kernel_dispatches += 1
            if dst in _DEVICE:
                self._scatter_device(
                    dst, rids[sel], layers[sel], slots[sel],
                    pay[:p], sc[:p], pay[p:], sc[p:], editor,
                )
            else:
                kp, ks = np.asarray(pay[:p]), np.asarray(sc[:p])
                vp, vs = np.asarray(pay[p:]), np.asarray(sc[p:])
                for j, r in enumerate(rids[sel]):
                    self.host_pages[int(r)] = (kp[j], ks[j], vp[j], vs[j])
                self._pool_slot[rids[sel]] = -2
                self._set_placement(rids[sel], dst)
                self._host_sentinel_insert(
                    rids[sel], layers[sel], slots[sel], kp, ks, bits, editor
                )
            if dst == WARM:
                kp_sz = int(np.prod(pay[:p].shape))
                sc_sz = int(np.prod(sc[:p].shape))
                for _ in range(p):
                    self.manager.update_measured_ratio(
                        WARM, 2.0 * (kp_sz / p) / (kp_sz / p + 4 * sc_sz / p)
                    )
        self.state = editor.commit(self.state)
        self._page_exists[rids] = True

    def _evict_coldest_warm(self, tenant: Optional[int] = None) -> bool:
        """Warm pool pressure: demote the coldest warm page to cold pool.
        ``tenant`` scopes the victim search to one tenant's pages (quota
        pressure frees quota only by evicting the quota holder's own pages).
        Returns False when there is nothing demotable."""
        hot = self.manager.telemetry.averaged_hotness(2)
        mask = (self.physical == WARM) & self._page_exists
        if tenant is not None:
            mask &= self.tenant_mask(tenant)
        warm_rids = np.where(mask)[0]
        if warm_rids.size == 0:
            return False
        victim = warm_rids[np.argmin(hot[warm_rids])]
        self.migrate(int(victim), COLD)
        return True

    # ------------------------------------------------- batched migration
    def plan_cohorts(
        self, rids: np.ndarray, dsts: np.ndarray
    ) -> List[Tuple[np.ndarray, int, int]]:
        """Normalize a migration batch into ordered (rids, src, dst) cohorts.

        Shared by the blocking executor (``migrate_batch``) and the async
        media pipeline. Dedups (last entry wins, the per-page loop's
        semantics), drops no-ops/missing/in-flight pages, runs the warm
        capacity + tenant-quota pre-passes, and phase-orders the cohorts so
        frees land before re-claims: device->host swap-outs first, then
        warm->cold demotions, cold->warm promotions, host->device swap-ins,
        and finally host<->host retranscodes.
        """
        rids = np.asarray(rids, np.int64)
        dsts = np.asarray(dsts, np.int64)
        if rids.size and np.unique(rids).size != rids.size:
            _, rev_first = np.unique(rids[::-1], return_index=True)
            idx = np.sort(rids.size - 1 - rev_first)
            rids, dsts = rids[idx], dsts[idx]
        keep = (
            self._page_exists[rids]
            & (self.physical[rids] != dsts)
            & (self.physical[rids] != INFLIGHT)
        )
        rids, dsts = rids[keep], dsts[keep]
        if rids.size == 0:
            return []
        srcs = self.physical[rids].copy()

        # Warm-capacity pre-pass.
        inflow = int((dsts == WARM).sum())
        freed = int((srcs == WARM).sum())
        deficit = inflow - (len(self._free_warm) + freed)
        if deficit > 0:
            hot = self.manager.telemetry.averaged_hotness(2)
            in_batch = np.zeros(self.n_regions, bool)
            in_batch[rids] = True
            cand = np.where((self.physical == WARM) & self._page_exists & ~in_batch)[0]
            take = cand[np.argsort(hot[cand])][:deficit]
            if take.size:
                rids = np.concatenate([take, rids])
                srcs = np.concatenate([np.full(take.size, WARM, np.int64), srcs])
                dsts = np.concatenate([np.full(take.size, COLD, np.int64), dsts])
                deficit -= take.size
            if deficit > 0:
                # Still short: the coldest warm-bound pages spill to cold.
                warm_bound = np.where(dsts == WARM)[0]
                spill = warm_bound[np.argsort(hot[rids[warm_bound]])][:deficit]
                dsts[spill] = COLD
                still = dsts != srcs
                rids, srcs, dsts = rids[still], srcs[still], dsts[still]
        rids, srcs, dsts = self._quota_pre_pass(rids, srcs, dsts)
        if rids.size == 0:
            return []

        def phase(s: int, d: int) -> int:
            if s in _DEVICE and d not in _DEVICE:
                return 0  # device -> host: frees pool slots first
            if s == WARM and d == COLD:
                return 1
            if s == COLD and d == WARM:
                return 2
            if s not in _DEVICE and d in _DEVICE:
                return 3  # host -> device swap-in (through the pools)
            return 4  # host <-> host retranscode

        pairs = sorted(
            {(int(s), int(d)) for s, d in zip(srcs, dsts)},
            key=lambda p: (phase(*p), p),
        )
        return [
            (rids[(srcs == s) & (dsts == d)], s, d) for s, d in pairs
        ]

    def _quota_pre_pass(self, rids, srcs, dsts):
        """Tenant-quota capacity pre-pass for the device pools.

        Warm: a tenant whose warm inflow exceeds its remaining quota (plus
        its own in-batch warm frees) first demotes its own coldest
        non-batch warm pages, then spills its coldest warm-bound pages to
        the cold pool. Cold: overflow past the tenant's cold quota (after
        in-batch cold frees) spills straight to the int4 host tier — same
        direction the single-page ``_insert`` path takes, so the blocking
        executor can never hit a quota-exhausted alloc mid-cohort."""
        if self._alloc["warm"].tenant_quota is not None and (dsts == WARM).any():
            hot = self.manager.telemetry.averaged_hotness(2)
            tenants_r = self.slot_tenant[self._rid_slot[rids]]
            for t in np.unique(tenants_r[dsts == WARM]):
                t = int(t)
                mine = tenants_r == t
                inflow = int(((dsts == WARM) & mine).sum())
                freed = int(((srcs == WARM) & mine).sum())
                deficit = inflow - (self._quota_headroom("warm", t) + freed)
                if deficit <= 0:
                    continue
                in_batch = np.zeros(self.n_regions, bool)
                in_batch[rids] = True
                cand = np.where(
                    (self.physical == WARM)
                    & self._page_exists
                    & self.tenant_mask(t)
                    & ~in_batch
                )[0]
                take = cand[np.argsort(hot[cand])][:deficit]
                if take.size:
                    rids = np.concatenate([take, rids])
                    srcs = np.concatenate([np.full(take.size, WARM, np.int64), srcs])
                    dsts = np.concatenate([np.full(take.size, COLD, np.int64), dsts])
                    tenants_r = self.slot_tenant[self._rid_slot[rids]]
                    deficit -= take.size
                if deficit > 0:
                    mine = tenants_r == t
                    warm_bound = np.where((dsts == WARM) & mine)[0]
                    spill = warm_bound[np.argsort(hot[rids[warm_bound]])][:deficit]
                    dsts[spill] = COLD
                    still = dsts != srcs
                    rids, srcs, dsts = rids[still], srcs[still], dsts[still]
                    tenants_r = self.slot_tenant[self._rid_slot[rids]]
        if self._alloc["cold"].tenant_quota is not None and (dsts == COLD).any():
            hot = self.manager.telemetry.averaged_hotness(2)
            tenants_r = self.slot_tenant[self._rid_slot[rids]]
            for t in np.unique(tenants_r[dsts == COLD]):
                t = int(t)
                mine = tenants_r == t
                inflow = int(((dsts == COLD) & mine).sum())
                freed = int(((srcs == COLD) & mine).sum())
                deficit = inflow - (self._quota_headroom("cold", t) + freed)
                if deficit <= 0:
                    continue
                cold_bound = np.where((dsts == COLD) & mine)[0]
                spill = cold_bound[np.argsort(hot[rids[cold_bound]])][:deficit]
                dsts[spill] = HOST4
                still = dsts != srcs
                rids, srcs, dsts = rids[still], srcs[still], dsts[still]
                tenants_r = self.slot_tenant[self._rid_slot[rids]]
        return rids, srcs, dsts

    def migrate_batch(self, rids: np.ndarray, dsts: np.ndarray) -> int:
        """Execute a migration batch cohort-by-cohort, blocking (the serial
        oracle the async pipeline is equivalence-tested against). When
        promotions would overflow the warm pool even after in-batch frees,
        the coldest non-batch warm pages are demoted first; any remaining
        overflow lands in the cold pool (the per-page path's spill
        semantics). Returns pages actually moved."""
        cohorts = self.plan_cohorts(rids, dsts)
        if not cohorts:
            return 0
        editor = _TableEditor(self.state)
        moved = 0
        for crids, s, d in cohorts:
            self._exec_cohort(crids, s, d, editor)
            moved += int(crids.size)
        self.state = editor.commit(self.state)
        return moved

    def _exec_cohort(self, rids: np.ndarray, src: int, dst: int, editor: _TableEditor) -> None:
        """Move one (src, dst) cohort: gather -> (transcode | copy) -> scatter.
        Same-class device moves skip all three: row ownership transfers
        between the pools' allocators and the page tables are re-pointed —
        zero payload bytes move."""
        p = rids.size
        layers = rids // (self.bs * self.max_pages)
        slots = (rids // self.max_pages) % self.bs

        if self._same_class(src, dst):
            ps = self._pool_slot[rids]
            editor.remove(_POOL[src], layers, slots, ps)
            self._exchange_rows(src, dst, rids, ps)
            editor.insert(_POOL[dst], layers, slots, ps)
            self._set_placement(rids, dst)
            return

        # Gather all pages of the cohort into one [2P, T, KV, hd'] batch
        # (K pages then V pages, so one kernel dispatch covers both).
        if src in _DEVICE:
            pool = _POOL[src]
            ps = self._pool_slot[rids]
            k_pay, k_sc, v_pay, v_sc = self._gather_rows(pool, ps)
            editor.remove(pool, layers, slots, ps)
            for x in ps:
                self._free_slot(pool, int(x))
        else:
            self._invalidate_prefetch(rids)
            self._host_sentinel_remove(rids, layers, slots, editor)
            hp = [self.host_pages.pop(int(r)) for r in rids]
            k_pay = jnp.asarray(np.stack([h[0] for h in hp]))
            k_sc = jnp.asarray(np.stack([h[1] for h in hp]))
            v_pay = jnp.asarray(np.stack([h[2] for h in hp]))
            v_sc = jnp.asarray(np.stack([h[3] for h in hp]))

        if self._bits[src] != self._bits[dst]:
            pay, sc = kops.transcode_pages(
                jnp.concatenate([k_pay, v_pay]), jnp.concatenate([k_sc, v_sc]),
                self._bits[src], self._bits[dst],
            )
            self.kernel_dispatches += 1
            k_pay, v_pay = pay[:p], pay[p:]
            k_sc, v_sc = sc[:p], sc[p:]
        # else: same-codec fast path — raw media copy, no transcode dispatch.

        if dst in _DEVICE:
            self._scatter_device(dst, rids, layers, slots, k_pay, k_sc, v_pay, v_sc, editor)
        else:
            kp, ks = np.asarray(k_pay), np.asarray(k_sc)
            vp, vs = np.asarray(v_pay), np.asarray(v_sc)
            for i, r in enumerate(rids):
                self.host_pages[int(r)] = (kp[i], ks[i], vp[i], vs[i])
            self._pool_slot[rids] = -2
            self._set_placement(rids, dst)
            self._host_sentinel_insert(rids, layers, slots, kp, ks, self._bits[dst], editor)

    def _scatter_device(self, dst, rids, layers, slots, k_pay, k_sc, v_pay, v_sc, editor):
        pool = _POOL[dst]
        new_ps = np.array([self._alloc_slot(pool, int(r)) for r in rids], np.int64)
        self._scatter_rows(pool, new_ps, k_pay, k_sc, v_pay, v_sc)
        editor.insert(pool, layers, slots, new_ps)
        self._pool_slot[rids] = new_ps
        self._set_placement(rids, dst)

    # ------------------------------------- phase-split executor (pipeline)
    # The async media pipeline drives one cohort through these three
    # callbacks across successive engine decode steps. Payloads cross the
    # phase boundaries as numpy dicts so host-media cohorts can round-trip
    # through the pinned staging ring bit-exactly.
    def stage_cohort(
        self, rids: np.ndarray, src: int, dst: Optional[int] = None
    ) -> Dict[str, np.ndarray]:
        """Phase 1: gather the cohort's payloads and retire them from the
        source tier. Pages go in-flight: out of every placement mask until
        ``commit_cohort`` lands them, and — like host-tier pages always are
        — unreadable by decode steps for those few ticks. That bounded
        access-skip is the async pipeline's quality cost; the serial oracle
        pays a blocked window boundary instead.

        When ``dst`` is known and shares the source's codec class, staging
        degenerates to a table edit: the payload rows stay in place (and
        allocated to src) in the shared class buffer and a ``class_rows``
        marker rides the pipeline instead of bytes."""
        rids = np.asarray(rids, np.int64)
        layers = rids // (self.bs * self.max_pages)
        slots = (rids // self.max_pages) % self.bs
        st = self.state
        if dst is not None and self._same_class(src, dst):
            ps = self._pool_slot[rids]
            editor = _TableEditor(st)
            editor.remove(_POOL[src], layers, slots, ps)
            self.state = editor.commit(st)
            # Rows remain owned by src's allocator until commit exchanges
            # them; ``_pool_slot`` keeps pointing at the resident rows.
            self.physical[rids] = INFLIGHT
            return {"class_rows": ps.copy()}
        if src in _DEVICE:
            pool = _POOL[src]
            ps = self._pool_slot[rids]
            kp, ks, vp, vs = self._gather_rows(pool, ps)
            payload = {
                "k_pay": np.asarray(kp),
                "k_sc": np.asarray(ks),
                "v_pay": np.asarray(vp),
                "v_sc": np.asarray(vs),
            }
            editor = _TableEditor(st)
            editor.remove(pool, layers, slots, ps)
            self.state = editor.commit(st)
            for x in ps:
                self._free_slot(pool, int(x))
        else:
            self._invalidate_prefetch(rids)
            self._host_sentinel_remove(rids, layers, slots)
            hp = [self.host_pages.pop(int(r)) for r in rids]
            payload = {
                "k_pay": np.stack([h[0] for h in hp]),
                "k_sc": np.stack([h[1] for h in hp]),
                "v_pay": np.stack([h[2] for h in hp]),
                "v_sc": np.stack([h[3] for h in hp]),
            }
        self.physical[rids] = INFLIGHT
        self._pool_slot[rids] = -3
        return payload

    def peek_cohort(self, rids: np.ndarray, src: int) -> Dict[str, np.ndarray]:
        """Non-destructive gather for speculative staging: the source copy
        stays resident and readable — prefetch is a shadow copy, exactly
        like OS readahead into the page cache. Host tiers only (the swap-in
        latency being hidden is the host-media round trip)."""
        assert src not in _DEVICE, "prefetch sources are host tiers"
        rids = np.asarray(rids, np.int64)
        hp = [self.host_pages[int(r)] for r in rids]
        return {
            "k_pay": np.stack([h[0] for h in hp]),
            "k_sc": np.stack([h[1] for h in hp]),
            "v_pay": np.stack([h[2] for h in hp]),
            "v_sc": np.stack([h[3] for h in hp]),
        }

    def drop_source_copies(self, rids: np.ndarray, src: int) -> None:
        """Retire the source copies of prestaged (prefetched) pages at
        commit time: their shadow copy — already read and transcoded
        mid-window — replaces the boundary's source read entirely."""
        assert src not in _DEVICE, "prefetch sources are host tiers"
        rids = np.asarray(rids, np.int64)
        layers = rids // (self.bs * self.max_pages)
        slots = (rids // self.max_pages) % self.bs
        self._host_sentinel_remove(rids, layers, slots)
        for r in rids:
            self.host_pages.pop(int(r), None)
        self.physical[rids] = INFLIGHT
        self._pool_slot[rids] = -3

    def transcode_cohort(
        self, payload: Dict[str, np.ndarray], src: int, dst: int
    ) -> Dict[str, np.ndarray]:
        """Phase 2: one fused transcode dispatch for the whole cohort (K and
        V stacked); the same-codec fast path is a raw media copy, and a
        same-class ``class_rows`` marker passes through untouched (the
        payload never left the class buffer)."""
        if "class_rows" in payload:
            return payload
        if self._bits[src] == self._bits[dst]:
            return payload
        p = payload["k_pay"].shape[0]
        pay, sc = kops.transcode_pages(
            jnp.concatenate([jnp.asarray(payload["k_pay"]), jnp.asarray(payload["v_pay"])]),
            jnp.concatenate([jnp.asarray(payload["k_sc"]), jnp.asarray(payload["v_sc"])]),
            self._bits[src], self._bits[dst],
        )
        self.kernel_dispatches += 1
        return {
            "k_pay": np.asarray(pay[:p]), "k_sc": np.asarray(sc[:p]),
            "v_pay": np.asarray(pay[p:]), "v_sc": np.asarray(sc[p:]),
        }

    def _claim_fits(self, pool: str, rids: np.ndarray) -> np.ndarray:
        """Greedy in-order claim check: True where the rid could take a
        ``pool`` slot right now, honoring both the global free list and the
        rid's tenant quota. Shared by batched ingestion and the async
        commit phase so the two fit/spill decisions cannot drift."""
        a = self._alloc[pool]
        glob = len(a._free)
        claimed: Dict[int, int] = {}
        out = np.zeros(len(rids), bool)
        for i, r in enumerate(rids):
            t = self._tenant_of_rid(int(r))
            c = claimed.get(t, 0)
            if glob > 0 and self._pool_headroom(pool, t) - c > 0:
                out[i] = True
                claimed[t] = c + 1
                glob -= 1
        return out

    def commit_cohort(
        self, rids: np.ndarray, payload: Dict[str, np.ndarray], src: int, dst: int
    ) -> np.ndarray:
        """Phase 3: scatter into the destination tier. Device headroom is
        re-checked at commit time (appends may have raced the in-flight
        cohort); pages that no longer fit spill down-tier, re-transcoding
        the spilled sub-batch when the spill crosses codecs. Returns the
        per-rid level actually landed (spills included) so the pipeline can
        bill the devices that really absorbed the writes."""
        rids = np.asarray(rids, np.int64)
        if "class_rows" in payload:
            return self._commit_class_rows(rids, payload["class_rows"], src, dst)
        actual = np.full(rids.size, dst, np.int64)
        if dst in _DEVICE:
            fits = self._claim_fits(_POOL[dst], rids)
            fi = np.where(fits)[0]
            if fi.size:
                frids = rids[fi]
                layers = frids // (self.bs * self.max_pages)
                slots = (frids // self.max_pages) % self.bs
                editor = _TableEditor(self.state)
                self._scatter_device(
                    dst, frids, layers, slots,
                    payload["k_pay"][fi], payload["k_sc"][fi],
                    payload["v_pay"][fi], payload["v_sc"][fi], editor,
                )
                self.state = editor.commit(self.state)
            sp = np.where(~fits)[0]
            if sp.size:
                sub = {k: v[sp] for k, v in payload.items()}
                spill_dst = self._spill_target(dst)
                sub = self.transcode_cohort(sub, dst, spill_dst)
                self._spill_depth += 1
                try:
                    actual[sp] = self.commit_cohort(rids[sp], sub, src, spill_dst)
                finally:
                    self._spill_depth -= 1
            return actual
        kp, ks = np.asarray(payload["k_pay"]), np.asarray(payload["k_sc"])
        vp, vs = np.asarray(payload["v_pay"]), np.asarray(payload["v_sc"])
        for i, r in enumerate(rids):
            self.host_pages[int(r)] = (kp[i], ks[i], vp[i], vs[i])
        self._pool_slot[rids] = -2
        self._set_placement(rids, dst)
        layers = rids // (self.bs * self.max_pages)
        slots = (rids // self.max_pages) % self.bs
        self._host_sentinel_insert(rids, layers, slots, kp, ks, self._bits[dst])
        return actual

    def _commit_class_rows(
        self, rids: np.ndarray, ps: np.ndarray, src: int, dst: int
    ) -> np.ndarray:
        """Commit a same-class marker cohort: exchange row ownership into the
        destination pool and re-point the page tables — zero payload motion.
        Pages that no longer fit at commit time (appends raced the cohort)
        fall back to the byte-moving path: their rows are gathered, freed
        from src and the sub-batch spills down-tier exactly like a regular
        commit overflow."""
        ps = np.asarray(ps, np.int64)
        actual = np.full(rids.size, dst, np.int64)
        fits = self._claim_fits(_POOL[dst], rids)
        fi = np.where(fits)[0]
        if fi.size:
            frids, fps = rids[fi], ps[fi]
            layers = frids // (self.bs * self.max_pages)
            slots = (frids // self.max_pages) % self.bs
            editor = _TableEditor(self.state)
            self._exchange_rows(src, dst, frids, fps)
            editor.insert(_POOL[dst], layers, slots, fps)
            self.state = editor.commit(self.state)
            self._set_placement(frids, dst)
        sp = np.where(~fits)[0]
        if sp.size:
            srids, sps = rids[sp], ps[sp]
            spill_dst = self._spill_target(dst)
            if spill_dst == src:
                # Spilling back into the source pool: the rows never left it;
                # reinsert the table entries and the move becomes a no-op.
                layers = srids // (self.bs * self.max_pages)
                slots = (srids // self.max_pages) % self.bs
                editor = _TableEditor(self.state)
                editor.insert(_POOL[src], layers, slots, sps)
                self.state = editor.commit(self.state)
                self._set_placement(srids, src)
                actual[sp] = src
            else:
                kp, ks, vp, vs = self._gather_rows(_POOL[src], sps)
                sub = {
                    "k_pay": np.asarray(kp), "k_sc": np.asarray(ks),
                    "v_pay": np.asarray(vp), "v_sc": np.asarray(vs),
                }
                for x in sps:
                    self._free_slot(_POOL[src], int(x))
                self._pool_slot[srids] = -3
                sub = self.transcode_cohort(sub, src, spill_dst)
                self._spill_depth += 1
                try:
                    actual[sp] = self.commit_cohort(srids, sub, src, spill_dst)
                finally:
                    self._spill_depth -= 1
        return actual

    def _spill_target(self, dst: int) -> int:
        """Down-tier destination for pages that no longer fit at commit time.
        The healthy ladder is WARM -> COLD -> HOST4; when the host media
        device is down (quarantined), a COLD overflow is redirected sideways
        into WARM instead of writing to a dead device — unless WARM is also
        full, in which case HOST4 is the forced terminal write (counted, so
        the storm benchmark can assert it never fires)."""
        spill = COLD if dst == WARM else HOST4
        if (
            spill == HOST4
            and self._spill_depth == 0
            and self._dev_names[HOST4] in self._down_devices
            and self._alloc["warm"].used < self._alloc["warm"].capacity
        ):
            # Redirect only at the top of a spill chain: a nested overflow
            # (e.g. tenant quotas blocking both device pools) falls through
            # to the forced terminal host write instead of ping-ponging
            # between the two device pools forever.
            self.fault_spill_redirects += 1
            return WARM
        return spill

    def device_of(self, level: int) -> str:
        """Backing-media device name for a placement level."""
        return self._dev_names[int(level)]

    def page_stored_bytes(self, level: int) -> int:
        """Media bytes one page occupies at a placement level."""
        return int(self._page_stored_bytes[int(level)])

    def on_pipeline_drained(self) -> None:
        """Pipeline hook after a batch fully commits: reconcile the
        policy's desired placement with physical reality (spills included)
        and feed the executed media busy time back to the manager as
        contention pressure."""
        for rids in self._pending_reconcile:
            ex = rids[self._page_exists[rids] & (self.physical[rids] != INFLIGHT)]
            self.manager.placement[ex] = self.physical[ex]
        self._pending_reconcile.clear()
        # Speculative traffic is billed on the queues (TCO/media report,
        # arbiter budgets) but excluded from the contention feedback that
        # shapes placement: prefetch must never change where pages land,
        # only when their bytes move.
        spec = self.pipeline.prefetch_busy_by_device
        busy = {
            n: q.busy_s - spec.get(n, 0.0) for n, q in self.media_queues.items()
        }
        delta = {
            n: busy[n] - self._media_busy_snapshot.get(n, 0.0) for n in busy
        }
        self._media_busy_snapshot = busy
        window_s = self.manager.cfg.window_steps * self.pipeline.step_period_s
        self.manager.note_media_charges(delta, window_s)

    def drain_migrations(self) -> int:
        """Block until every in-flight migration cohort commits."""
        if self.pipeline.busy:
            return self.pipeline.drain()
        return 0

    # ------------------------------------------------ speculative prefetch
    def prefetch_tick(self) -> bool:
        """One decode step's worth of speculative work: emit this window's
        warming-page cohort (at most one non-empty emission per window) and
        advance speculative staging by one phase. Strictly lower priority
        than demand migration: a no-op while demand cohorts are in flight."""
        if not self.prefetch_enabled or self.pipeline.busy:
            return False
        if not self._prefetch_window_emitted:
            # Retry until the accumulating window shows a rising cohort
            # (telemetry grows step by step); one emission per window.
            if self._emit_prefetch():
                self._prefetch_window_emitted = True
        return self.pipeline.tick()

    def _emit_prefetch(self) -> int:
        """Ask the predictor for warming host pages and queue their raw
        bytes for speculative staging. No destination is predicted — the
        staged copy is source-codec, so it serves whatever tier the
        boundary plan picks (promotion, demotion or retranscode)."""
        eligible = (
            ((self.physical == HOST8) | (self.physical == HOST4)) & self._page_exists
        )
        for rid in self.pipeline.speculative_rids():
            eligible[rid] = False
        if not eligible.any():
            return 0
        fast = int((((self.physical == WARM) | (self.physical == COLD))).sum())
        cand = self.manager.prefetch_candidates(
            eligible, top_k=max(fast, 1), max_regions=self.prefetch_max_pages
        )
        if cand.size == 0:
            return 0
        cohorts = [
            (cand[self.physical[cand] == s], int(s))
            for s in (HOST8, HOST4)
            if bool((self.physical[cand] == s).any())
        ]
        return self.pipeline.submit_prefetch(cohorts)

    # ------------------------------------------------- per-page migration
    def migrate(self, rid: int, dst: int) -> None:
        """Per-page migration path (equivalence oracle + single evictions)."""
        src = int(self.physical[rid])
        if src == dst or src == INFLIGHT or not self._page_exists[rid]:
            return
        layer, slot, page = self.rid_coords(rid)
        k, v = self._fetch_dense(rid, layer, slot, page)
        self._remove(rid, layer, slot, page)
        self._insert(rid, layer, slot, page, k, v, dst)

    def _fetch_dense(self, rid, layer, slot, page):
        """Decompress a page from wherever it lives (f32)."""
        src = int(self.physical[rid])
        ps = int(self._pool_slot[rid])
        st = self.state
        self.kernel_dispatches += 2
        if src in _DEVICE:
            cls, bits = self._cls[_POOL[src]], self._bits[src]
            k = kref.dequant_kv_page(
                getattr(st, f"{cls}_k")[ps], getattr(st, f"{cls}_k_scales")[ps], bits
            )
            v = kref.dequant_kv_page(
                getattr(st, f"{cls}_v")[ps], getattr(st, f"{cls}_v_scales")[ps], bits
            )
        else:
            kp, ks, vp, vs = self.host_pages[rid]
            bits = 8 if src == HOST8 else 4
            k = kref.dequant_kv_page(jnp.asarray(kp), jnp.asarray(ks), bits)
            v = kref.dequant_kv_page(jnp.asarray(vp), jnp.asarray(vs), bits)
        return k, v

    def _remove(self, rid, layer, slot, page):
        src = int(self.physical[rid])
        ps = int(self._pool_slot[rid])
        if src == WARM:
            # Drop from table by swapping with the last entry.
            self._table_remove("warm", layer, slot, ps)
            self._free_slot("warm", ps)
        elif src == COLD:
            self._table_remove("cold", layer, slot, ps)
            self._free_slot("cold", ps)
        else:
            self._invalidate_prefetch(np.array([rid], np.int64))
            self._host_sentinel_remove(
                np.array([rid], np.int64), np.array([layer]), np.array([slot])
            )
            self.host_pages.pop(rid, None)
        self._pool_slot[rid] = -1

    def _table_remove(self, pool: str, layer: int, slot: int, pool_slot: int):
        st = self.state
        table = getattr(st, f"{pool}_table")
        n = int(getattr(st, f"{pool}_n")[layer, slot])
        row = np.array(table[layer, slot][:n])  # writable copy
        idx = int(np.where(row == pool_slot)[0][0])
        row[idx] = row[n - 1]
        row[n - 1] = 0
        new_table = table.at[layer, slot, :n].set(jnp.asarray(row))
        kw = {f"{pool}_table": new_table,
              f"{pool}_n": getattr(st, f"{pool}_n").at[layer, slot].set(n - 1)}
        self.state = dataclasses.replace(st, **kw)

    def _insert(self, rid, layer, slot, page, k, v, dst):
        st = self.state
        tenant = self._tenant_of_rid(rid)
        if dst == WARM and self._pool_headroom("warm", tenant) == 0:
            scoped = tenant if self._quota_headroom("warm", tenant) == 0 else None
            if not self._evict_coldest_warm(tenant=scoped):
                dst = COLD  # nothing demotable; spill to the next tier
            elif self._pool_headroom("warm", tenant) == 0:
                dst = COLD  # eviction freed no usable headroom
            st = self.state
        if dst == COLD and self._pool_headroom("cold", tenant) == 0:
            dst = HOST4  # cold quota exhausted; spill to the host tier
        if dst in _DEVICE:
            pool = _POOL[dst]
            ps = self._alloc_slot(pool, rid)
            kp, ks, vp, vs = self._quant_page(k, v, self._bits[dst])
            self._scatter_rows(pool, ps, kp, ks, vp, vs)
            st = self.state
            n = int(getattr(st, f"{pool}_n")[layer, slot])
            st = dataclasses.replace(
                st,
                **{
                    f"{pool}_table": getattr(st, f"{pool}_table").at[layer, slot, n].set(ps),
                    f"{pool}_n": getattr(st, f"{pool}_n").at[layer, slot].set(n + 1),
                },
            )
        else:
            bits = self._bits[dst]
            kp, ks, vp, vs = self._quant_page(k, v, bits)
            self.host_pages[rid] = tuple(np.asarray(x) for x in (kp, ks, vp, vs))
            ps = -2
        self.state = st
        self._set_placement(rid, dst)
        self._pool_slot[rid] = ps
        if dst not in _DEVICE:
            self._host_sentinel_insert(
                np.array([rid], np.int64), np.array([layer]), np.array([slot]),
                np.asarray(kp)[None], np.asarray(ks)[None], bits,
            )

    # ------------------------------------------------------------ release
    def release_slot_pages(self, slot: int) -> None:
        """Request finished: free all of one batch slot's pages, batched.
        If any of THIS slot's pages ride an in-flight migration cohort the
        pipeline is drained first (they must not strand in the staging
        ring); other slots' cohorts keep overlapping undisturbed."""
        if self.pipeline.busy and bool(
            (self.physical[self._rid_slot == slot] == INFLIGHT).any()
        ):
            self.pipeline.drain()
        rids = np.array(
            [self.rid(layer, slot, page)
             for layer in range(self.la) for page in range(self.max_pages)],
            np.int64,
        )
        rids = rids[self._page_exists[rids]]
        self._invalidate_prefetch(rids)
        for r in rids:
            src = int(self.physical[r])
            ps = int(self._pool_slot[r])
            if src == WARM:
                self._free_slot("warm", ps)
            elif src == COLD:
                self._free_slot("cold", ps)
            else:
                if self._host_slot[r] >= 0:
                    layer = int(r) // (self.bs * self.max_pages)
                    self._host_alloc[layer].free(int(self._host_slot[r]))
                self.host_pages.pop(int(r), None)
        self._pool_slot[rids] = -1
        self._host_slot[rids] = -1
        self._page_exists[rids] = False
        self.physical[rids] = 0
        self.manager.placement[rids] = 0
        st = self.state
        self.state = dataclasses.replace(
            st,
            warm_n=st.warm_n.at[:, slot].set(0),
            cold_n=st.cold_n.at[:, slot].set(0),
            host_n=st.host_n.at[:, slot].set(0),
        )

    # ------------------------------------------- preemption-to-host-tier
    # The serving frontend parks a victim slot's KV on the host tier when a
    # higher-SLA request needs its batch slot, and swaps it back in on
    # resume — zero re-prefill. Three phases: demote (device pages -> same
    # codec host tier through the media pipeline, billed like any other
    # demotion), park (lift payloads + recent window out of the region
    # space), restore (re-register under a free slot, swap device-bound
    # pages back in through the pipeline).
    def slot_rids(self, slot: int) -> np.ndarray:
        """All live region ids currently owned by ``slot``."""
        return np.where(self._page_exists & (self._rid_slot == slot))[0]

    def demote_slot_to_host(self, slot: int) -> Dict[int, int]:
        """Preemption phase 1: demote every device-resident page of ``slot``
        to the host tier of its OWN codec class (warm int8 -> HOST8, cold
        int4 -> HOST4 — a raw media copy with no transcode dispatch, so the
        stored payload survives bit-exactly). Runs through the media
        pipeline, so media-queue bytes and kernel dispatches are billed
        exactly like a window boundary's demotion cohorts. Returns
        rid -> pre-demotion placement (``restore_slot``'s swap-in plan)."""
        if self.pipeline.busy:
            self.pipeline.drain()
        rids = self.slot_rids(slot)
        orig = {int(r): int(self.physical[r]) for r in rids}
        on_dev = rids[np.isin(self.physical[rids], _DEVICE)]
        if on_dev.size:
            bits = np.array([self._bits[int(s)] for s in self.physical[on_dev]])
            dsts = np.where(bits == 8, HOST8, HOST4).astype(np.int64)
            cohorts = self.plan_cohorts(on_dev, dsts)
            self.pipeline.submit(cohorts)
            self.pipeline.drain()
        return orig

    def park_slot(
        self, slot: int, restore_levels: Optional[Dict[int, int]] = None
    ) -> ParkedSlot:
        """Preemption phase 2: detach the slot's (now host-resident) pages
        and its recent-window rows from the cache entirely. Host payload
        slots, sentinel rows and region ids all free — the batch slot is
        immediately reusable by another request. ``restore_levels`` (from
        ``demote_slot_to_host``) records where each page lives again after
        resume; pages it omits stay on their parked host tier."""
        if self.pipeline.busy:
            self.pipeline.drain()
        rids = self.slot_rids(slot)
        if bool(np.isin(self.physical[rids], _DEVICE).any()):
            raise ValueError(
                f"park_slot({slot}): device-resident pages remain — call "
                "demote_slot_to_host first"
            )
        restore_levels = restore_levels or {}
        self._invalidate_prefetch(rids)
        layers = rids // (self.bs * self.max_pages)
        slots_v = (rids // self.max_pages) % self.bs
        self._host_sentinel_remove(rids, layers, slots_v)
        pages = []
        for r in rids:
            r = int(r)
            layer, _, page = self.rid_coords(r)
            lvl = int(self.physical[r])
            pages.append(ParkedPage(
                layer=layer, page=page, host_level=lvl,
                restore_level=int(restore_levels.get(r, lvl)),
                payload=self.host_pages.pop(r),
            ))
        self._page_exists[rids] = False
        self.physical[rids] = 0
        self.manager.placement[rids] = 0
        self._pool_slot[rids] = -1
        self._host_slot[rids] = -1
        st = self.state
        parked = ParkedSlot(
            tenant=int(self.slot_tenant[slot]),
            pages=pages,
            recent_k=np.asarray(st.recent_k[:, slot]),
            recent_v=np.asarray(st.recent_v[:, slot]),
            recent_len=int(st.recent_len[slot]),
            total_len=int(st.total_len[slot]),
        )
        self.state = dataclasses.replace(
            st,
            host_n=st.host_n.at[:, slot].set(0),
            recent_len=st.recent_len.at[slot].set(0),
            total_len=st.total_len.at[slot].set(0),
        )
        return parked

    def restore_slot(self, slot: int, parked: ParkedSlot) -> int:
        """Resume phase: re-register a parked request's pages under ``slot``
        (which must hold none) and swap the previously device-resident ones
        back in through the media pipeline — same-codec raw copies again, so
        every payload lands bit-exactly where its codec class stores it.
        The recent window and positions restore verbatim; the next decode
        step continues as if the preemption never happened. Returns the
        number of pages restored."""
        if self.slot_rids(slot).size:
            raise ValueError(f"restore_slot({slot}): target slot still holds pages")
        if self.pipeline.busy:
            self.pipeline.drain()
        self.set_slot_tenant(slot, parked.tenant)
        # Re-insert host payloads in layer-major logical page order so table
        # rows append in the same order an uninterrupted run built them.
        pages = sorted(parked.pages, key=lambda pg: (pg.layer, pg.page))
        rids = np.array([self.rid(pg.layer, slot, pg.page) for pg in pages], np.int64)
        if rids.size:
            if bool(self._page_exists[rids].any()):
                raise ValueError(f"restore_slot({slot}): region ids already live")
            levels = np.array([pg.host_level for pg in pages], np.int64)
            for r, pg in zip(rids, pages):
                self.host_pages[int(r)] = pg.payload
            self._page_exists[rids] = True
            self._pool_slot[rids] = -2
            self.physical[rids] = levels
            self.manager.placement[rids] = levels
            layers = rids // (self.bs * self.max_pages)
            slots_v = (rids // self.max_pages) % self.bs
            for lvl in (HOST8, HOST4):
                sel = np.where(levels == lvl)[0]
                if sel.size:
                    kp = np.stack([pages[i].payload[0] for i in sel])
                    ks = np.stack([pages[i].payload[1] for i in sel])
                    self._host_sentinel_insert(
                        rids[sel], layers[sel], slots_v[sel], kp, ks, self._bits[lvl]
                    )
        # Recent window + positions land exactly as parked.
        st = self.state
        self.state = dataclasses.replace(
            st,
            recent_k=st.recent_k.at[:, slot].set(
                jnp.asarray(parked.recent_k).astype(st.recent_k.dtype)),
            recent_v=st.recent_v.at[:, slot].set(
                jnp.asarray(parked.recent_v).astype(st.recent_v.dtype)),
            recent_len=st.recent_len.at[slot].set(parked.recent_len),
            total_len=st.total_len.at[slot].set(parked.total_len),
        )
        swap = np.array(
            [i for i, pg in enumerate(pages) if pg.restore_level in _DEVICE],
            np.int64,
        )
        if swap.size:
            dsts = np.array([pages[i].restore_level for i in swap], np.int64)
            cohorts = self.plan_cohorts(rids[swap], dsts)
            self.pipeline.submit(cohorts)
            self.pipeline.drain()
        return int(rids.size)

    # ------------------------------------------------------------ telemetry
    def record_telemetry(self, telemetry: Dict[str, jax.Array]) -> None:
        """Fold per-step page masses into region hotness counts.

        telemetry[pool] : [L, B, MP] normalized masses; map each table entry
        back to its region id via the logical page order of the table.
        Vectorized with the same table->rid mapping trick as ``_plan``:
        a (layer, pool_slot) -> rid lookup array turns the per-page python
        loop into one fancy-indexed gather + ``np.add.at`` per pool.
        ``_fold_telemetry_loop`` is the per-page equivalence oracle.

        A "host" key (the fused kernel's would-have-touched sentinel mass)
        routes to ``manager.record_host_mass`` — the prefetch predictor's
        in-engine signal — NOT into the placement-driving access counts:
        host pages are never read in-step, so their skipped mass is the
        quality cost of the best-TCO tiers (tracked, reported) and feeding
        it to the placement model would break oracle-identical placements.
        """
        with self.spans.span("tkv.telemetry"):
            self.manager.record_access_counts(self._fold_telemetry(telemetry) * 1000.0)
            host_mass = telemetry.get("host")
            if host_mass is not None:
                folded = self._fold_host_mass(host_mass)
                self.quality_skipped_mass += float(folded.sum())
                self.manager.record_host_mass(folded * 1000.0)
        # Decode-side dispatch proxy: one fused launch per layer per step,
        # O(tiers) only when the per-pool oracle path is toggled on.
        self.attn_launches += self.la * kops.decode_launches_per_step(
            n_pools=len(_POOL)
        )
        held_rids = np.flatnonzero(
            self._page_exists & (self.physical >= WARM) & (self.physical <= HOST4)
        )
        held = np.bincount(held_rids // self.max_pages, minlength=self.la * self.bs)
        self.attn_walk_slots += kops.decode_walk_slots(held, self.max_pages, len(_POOL))
        self.attn_pages += int(held_rids.size)
        self.decode_steps_recorded += 1

    def _fold_table_mass(self, counts, mass, table, nvec, cap, live, slot_of) -> None:
        """Accumulate per-table-entry ``mass`` [L, B, M] onto region ids.

        Builds the (layer, pool_slot) -> rid lookup from ``live`` rids and
        their ``slot_of`` slots (slots come from one free list per layer
        scope, so a slot maps to at most one live rid), then gathers +
        ``np.add.at``s in one shot. The validity mask is threefold: prefix
        count (entries past n are stale), mapped rid exists, and the rid
        must belong to this (layer, slot) row (slot-identity guard)."""
        rid_of = np.full((self.la, cap), -1, np.int64)
        rid_of[live // (self.bs * self.max_pages), slot_of[live]] = live
        m = min(mass.shape[2], table.shape[2])
        entry = table[:, :, :m]  # [L,B,m]
        cand = rid_of[np.arange(self.la)[:, None, None], entry]
        valid = np.arange(m)[None, None, :] < nvec[..., None]
        valid &= cand >= 0
        valid &= ((cand // self.max_pages) % self.bs) == np.arange(self.bs)[None, :, None]
        np.add.at(counts, cand[valid], mass[:, :, :m][valid])

    def _fold_telemetry(self, telemetry: Dict[str, jax.Array]) -> np.ndarray:
        counts = np.zeros(self.n_regions)
        st = self.state
        for pool, placement in (("warm", WARM), ("cold", COLD)):
            live = np.where((self.physical == placement) & self._page_exists)[0]
            if live.size == 0:
                continue
            self._fold_table_mass(
                counts,
                np.asarray(telemetry[pool]),
                np.asarray(getattr(st, f"{pool}_table")),
                np.asarray(getattr(st, f"{pool}_n")),
                # Slots are global class-buffer rows; the lookup spans the
                # whole class buffer (ranges interleave after exchanges).
                getattr(st, f"{self._cls[pool]}_k").shape[0],
                live,
                self._pool_slot,
            )
        return counts

    def _fold_host_mass(self, mass) -> np.ndarray:
        """Fold sentinel would-have-touched masses [L, B, MPh] into region
        counts — the same gather as ``_fold_telemetry``, against the host
        sentinel table."""
        counts = np.zeros(self.n_regions)
        live = np.where(
            ((self.physical == HOST8) | (self.physical == HOST4)) & self._page_exists
        )[0]
        if live.size:
            st = self.state
            self._fold_table_mass(
                counts, np.asarray(mass), np.asarray(st.host_table),
                np.asarray(st.host_n), st.host_summary.shape[1], live,
                self._host_slot,
            )
        return counts

    def _fold_telemetry_loop(self, telemetry: Dict[str, jax.Array]) -> np.ndarray:
        """Per-page reference semantics for ``_fold_telemetry`` (oracle)."""
        counts = np.zeros(self.n_regions)
        st = self.state
        for pool, placement in (("warm", WARM), ("cold", COLD)):
            mass = np.asarray(telemetry[pool])  # [L,B,MP]
            table = np.asarray(getattr(st, f"{pool}_table"))
            nvec = np.asarray(getattr(st, f"{pool}_n"))
            slot_to_rid = {}
            pl = self.physical
            for rid in np.where((pl == placement) & self._page_exists)[0]:
                layer, slot, _ = self.rid_coords(rid)
                slot_to_rid[(layer, slot, int(self._pool_slot[rid]))] = rid
            for layer in range(self.la):
                for slot in range(self.bs):
                    n = int(nvec[layer, slot])
                    for j in range(n):
                        rid = slot_to_rid.get((layer, slot, int(table[layer, slot, j])))
                        if rid is not None:
                            counts[rid] += mass[layer, slot, j]
        return counts

    def _observe_adaptive_media(self) -> None:
        """Feed compressibility-adaptive media devices real encoded sizes.

        Runs at the window boundary only, after the pipeline has drained —
        both the serial oracle and the async path reach this point with
        byte-identical ``host_pages``, so the observations (and therefore
        the device's post-commit effective bandwidth and the manager's
        measured ratios) are mode-independent by construction. Mid-window
        decode steps never call this, honoring the ``AdaptiveMediaDevice``
        contract that in-window service times are fixed.

        The observation is the real line-compressibility of resident host
        payloads. The inline compressor is codec-agnostic — it sees byte
        streams, and narrows any 64-byte hardware line whose bytes (as
        two's-complement codewords) fit int4 range — so int8 and packed
        int4 payloads both narrow exactly when their content does (e.g.
        zero pad-tail pages halve; dense full-range pages don't). Scales
        ride uncompressed."""
        adaptive = adaptive_devices(self.media_queues)
        if not adaptive:
            return
        for name, dev in adaptive.items():
            levels = [
                lvl for lvl in (HOST8, HOST4) if self._dev_names[lvl] == name
            ]
            if not levels:
                dev.commit_window()
                continue
            nominal = 0
            wire = 0
            for lvl in levels:
                rids = np.nonzero((self.physical == lvl) & self._page_exists)[0]
                for rid in rids:
                    kp, ks, vp, vs = self.host_pages[int(rid)]
                    for pay in (kp, vp):
                        b = int(pay.size) * int(pay.dtype.itemsize)
                        nominal += b
                        q = np.ascontiguousarray(pay).reshape(-1).view(np.int8)
                        n_lines = q.size // kref.CXL_LINE_ELEMS
                        head = q[: n_lines * kref.CXL_LINE_ELEMS]
                        if n_lines:
                            lines = head.reshape(-1, kref.CXL_LINE_ELEMS)
                            narrow = (
                                np.abs(lines.astype(np.int32)).max(axis=1)
                                <= kref.CXL_NARROW_QMAX
                            )
                            n_narrow = int(narrow.sum())
                            wire += (
                                n_narrow * (kref.CXL_LINE_ELEMS // 2)
                                + (n_lines - n_narrow) * kref.CXL_LINE_ELEMS
                            )
                        wire += q.size - n_lines * kref.CXL_LINE_ELEMS
                    for sc in (ks, vs):
                        b = int(sc.size) * int(sc.dtype.itemsize)
                        nominal += b
                        wire += b
            if nominal > 0:
                dev.observe(float(nominal), float(wire))
                ratio = float(nominal) / float(max(wire, 1))
                self.manager.note_media_ratio(name, ratio)
                nominal_ratios = self.manager.tierset.ratios()
                for lvl in levels:
                    self.manager.update_measured_ratio(
                        lvl, nominal_ratios[lvl] * ratio
                    )
            dev.commit_window()

    def _advance_fault_window(self) -> None:
        """Advance the fault clock one window and fold fault telemetry in.

        Runs at the window boundary, after the pipeline drained and before
        the manager plans — the same point in both serial and async modes,
        so the fault schedule (keyed on this window index) is replayed
        identically regardless of migration mode. Down devices discovered
        here drive both the manager's tier quarantine (placement frontier)
        and the plan filter in ``end_window`` (deferred moves)."""
        self._fault_window += 1
        for q in self.media_queues.values():
            q.device.note_window(self._fault_window)
        self._down_devices = {
            q.device.name
            for q in self.media_queues.values()
            if q.device.down_now()
        }
        self.manager.note_devices_down(self._down_devices)
        p = self.pipeline
        cur = (p.fault_retries, p.corruptions_detected, p.cohorts_aborted)
        prev = self._fault_counter_snapshot
        self.manager.note_fault_events(
            retries=cur[0] - prev[0],
            corruptions=cur[1] - prev[1],
            aborted=cur[2] - prev[2],
        )
        self._fault_counter_snapshot = cur

    # --------------------------------------------------------- window logic
    def end_window(self):
        """Run the placement model over existing pages and execute the plan.

        Serial mode (the oracle): the batched cohort executor runs the plan
        to completion before returning — the window boundary blocks.

        Async mode: cohorts are submitted to the media pipeline and the
        boundary returns immediately; decode steps tick the pipeline and
        the desired/physical reconcile happens when the batch drains. A
        previous window's stragglers are drained first so the placement
        model never plans over in-flight pages.
        """
        span = self.spans.span
        with span("tkv.drain"):
            if self.pipeline.busy:
                self.pipeline.drain()
            if self.prefetch_enabled:
                # Speculation meets reality: finish staged speculative cohorts
                # into the held store before the plan is computed.
                self.pipeline.finish_speculative()
        self._observe_adaptive_media()
        if self.fault_plan is not None:
            self._advance_fault_window()
        with span("tkv.plan"):
            plan = self.manager.end_window()
        self._prefetch_window_emitted = False
        if plan.regions.size == 0:
            if self.prefetch_enabled:
                self.pipeline.discard_speculative()  # nothing to claim: all misses
            return plan, 0
        # Manager may recommend DRAM(0) for hot pages; KV pages instead go
        # warm (the closest legal tier — recent window plays DRAM's role).
        regions = np.asarray(plan.regions, np.int64)
        dst = plan.dst.copy()
        dst[dst == 0] = WARM
        if self._down_devices:
            # Defer plan entries whose source or destination device is down
            # BEFORE the mode split, so serial and async replay abort the
            # same moves (pages stay put; the reconcile below resets the
            # manager's view to physical and the next healthy window
            # re-plans them). The pipeline's own down-abort remains the
            # second line of defense for direct submissions (park/restore).
            down_idx = np.array(
                [
                    i
                    for i, n in enumerate(self._dev_names)
                    if n in self._down_devices
                ],
                np.int64,
            )
            bad = np.isin(self.physical[regions], down_idx) | np.isin(dst, down_idx)
            self.fault_deferred_pages += int(bad.sum())
            regions, dst = regions[~bad], dst[~bad]
        if self.async_migration:
            with span("tkv.submit"):
                cohorts = self.plan_cohorts(regions, dst)
                prestaged: Dict[int, Dict[str, np.ndarray]] = {}
                if self.prefetch_enabled:
                    # Claim held pages the plan confirmed (hits — their demand
                    # stage pays no source read); everything else was
                    # mispredicted and is discarded, returning the ring credits.
                    for crids, s, _d in cohorts:
                        if s not in _DEVICE:
                            prestaged.update(self.pipeline.claim_prefetched(crids, s))
                    self.pipeline.discard_speculative()
                self._pending_reconcile.append(np.asarray(plan.regions, np.int64))
                queued = self.pipeline.submit(cohorts, prestaged=prestaged or None)
            if not self.pipeline.busy:
                # Empty plan after pre-passes: reconcile immediately.
                self.on_pipeline_drained()
            return plan, queued
        with span("tkv.submit"):
            moved = self.migrate_batch(regions, dst)
        # The executor wrote actual placements (incl. spills) back into
        # manager.placement so the cost model prices reality; also reconcile
        # planned no-ops (e.g. DRAM-recommended pages already sitting warm)
        # and fault-deferred moves (pages stay at their current device).
        ex = plan.regions[self._page_exists[plan.regions]]
        self.manager.placement[ex] = self.physical[ex]
        return plan, moved

    # ------------------------------------------------------------- metrics
    def hbm_bytes(self) -> int:
        st = self.state
        tot = 0
        for name in ("c8_k", "c8_k_scales", "c8_v", "c8_v_scales",
                     "c4_k", "c4_k_scales", "c4_v", "c4_v_scales",
                     "recent_k", "recent_v"):
            a = getattr(st, name)
            tot += a.size * a.dtype.itemsize
        return tot

    def tco_usd(self, tenant: Optional[int] = None) -> float:
        """Memory TCO of *existing* pages under the current placement,
        optionally restricted to one tenant's pages."""
        exists = self._page_exists
        if tenant is not None:
            exists = exists & self.tenant_mask(tenant)
        if not exists.any():
            return 0.0
        costs = tco.usd_per_region(
            self.manager.tierset, self.manager.region_bytes, self.manager.measured_ratios
        )
        return float(costs[self.manager.placement[exists]].sum())

    def tco_savings_pct(self, tenant: Optional[int] = None) -> float:
        """Savings vs holding every existing page uncompressed in HBM."""
        exists = self._page_exists
        if tenant is not None:
            exists = exists & self.tenant_mask(tenant)
        n = int(exists.sum())
        if n == 0:
            return 0.0
        mx = tco.tco_max(n, self.manager.region_bytes)
        return 100.0 * (mx - self.tco_usd(tenant)) / mx
