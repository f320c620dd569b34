"""Serving engine: continuous batching over slots + tiered KV cache.

Request lifecycle: queue -> slot assignment -> prefill (dense, then pages
compress into the warm tier) -> decode steps (tiered attention, telemetry)
-> window boundary (TierScape placement) -> completion frees pages.

One engine is one replica on one device: its parameters, its tiered KV
state and its jitted decode step live on the device it is given, and every
public method runs with that device as JAX's default, so nothing it creates
lands anywhere else. On a TPU the decode step runs the compiled fused
attention kernel; elsewhere it runs the jnp oracle (``kernels.ops.compiled``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ParallelConfig, TierScapeRunConfig
from repro.core.manager import ManagerConfig
from repro.kernels import ops as kops
from repro.launch.mesh import make_mesh
from repro.models.transformer import Model, _attn_layer_count
from repro.runtime import serve as serve_rt
from repro.serving.kv_cache import (
    ParkedSlot,
    TieredKVCache,
)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # [S] int32
    max_new_tokens: int
    tenant: int = 0  # owning tenant (engine serves interleaved tenant traffic)
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class PreemptedRequest:
    """A request evicted from its batch slot with its KV parked on the host
    tier (plus SSM side-state for hybrid archs). ``TieredEngine.resume_into``
    swaps it back in with zero re-prefilled tokens."""

    request: Request
    parked: ParkedSlot
    ssm_conv: Optional[np.ndarray] = None
    ssm_state: Optional[np.ndarray] = None


@dataclasses.dataclass
class EngineStats:
    steps: int = 0
    windows: int = 0
    migrations: int = 0
    completed: int = 0
    # Frontend preemption-to-host-tier accounting: slots vacated for a
    # higher-SLA arrival, requests swapped back in from parked host pages,
    # pages restored by those swap-ins, and prompt tokens re-prefilled for
    # an already-started request (the frontend contract keeps this at 0 —
    # resume restores pages instead of recomputing them).
    preemptions: int = 0
    resumes: int = 0
    resumed_pages: int = 0
    re_prefill_tokens: int = 0
    # Decode steps retired while a migration cohort was in flight (async
    # media pipeline) — the numerator of overlap efficiency.
    overlapped_steps: int = 0
    # Speculative prefetch: pages staged ahead / confirmed / mispredicted.
    prefetch_staged: int = 0
    prefetch_hits: int = 0
    prefetch_misses: int = 0
    # Decode-attention Pallas launches billed by the cache's dispatch proxy
    # (fused: n_layers per step, O(1) in tier count; per-pool oracle:
    # n_layers * n_pools).
    attn_launches: int = 0
    # The fused kernel's page walk, billed by the cache from host-side page
    # counts: page slots its grid visits and pages it attends (pool pages
    # and host sentinels), summed over layers and steps.
    attn_walk_slots: int = 0
    attn_pages: int = 0
    tco_savings_pct: float = 0.0
    completed_by_tenant: Dict[int, int] = dataclasses.field(default_factory=dict)
    tco_savings_by_tenant: Dict[int, float] = dataclasses.field(default_factory=dict)


def _on_device(method):
    """Run an engine method with the engine's device as JAX's default, so
    every array it creates (uploads, eager ops, kernel calls) lands there."""

    @functools.wraps(method)
    def wrapped(self, *args, **kwargs):
        with jax.default_device(self.device):
            return method(self, *args, **kwargs)

    return wrapped


class TieredEngine:
    """One replica on one device, for attention/hybrid archs with tiered KV.

    ``device`` is the device the replica runs on (the first device when not
    given); ``params`` are placed there."""

    def __init__(
        self,
        model: Model,
        params,
        batch_slots: int = 4,
        page_tokens: int = 16,
        max_seq_len: int = 512,
        recent_window: int = 32,
        ts: Optional[TierScapeRunConfig] = None,
        device: Optional[jax.Device] = None,
    ):
        cfg = model.cfg
        assert cfg.has_attention, "tiered KV serving needs attention layers"
        self.device = device if device is not None else jax.devices()[0]
        self.mesh = make_mesh((1, 1), ("data", "model"), devices=[self.device])
        self.model = model
        self.params = jax.device_put(params, self.device)
        self.cfg = cfg
        self.bs = batch_slots
        self.pt = page_tokens
        self.recent_window = recent_window
        self.max_seq_len = max_seq_len
        ts = ts or TierScapeRunConfig(enabled=True)
        self.ts = ts
        self.la = _attn_layer_count(cfg)

        mgr_cfg = ManagerConfig(
            policy=ts.policy,
            alpha=ts.alpha,
            hotness_threshold=ts.hotness_threshold,
            window_steps=ts.window_steps,
        )
        fault_plan = getattr(ts, "fault_plan", None)
        if fault_plan is None and getattr(ts, "faults", False):
            # REPRO_FAULTS=1 chaos soak: synthesize the default
            # transient+corruption plan on the host media device — fully
            # recovered and billing-neutral, so every tier-1 expectation
            # holds while the retry/repair paths run hot.
            from repro.media.faults import default_plan

            fault_plan = default_plan(
                getattr(ts, "host_media_device", "") or "host_dram_pcie"
            )
        with jax.default_device(self.device):
            self.cache = TieredKVCache(
                cfg,
                self.la,
                batch_slots,
                page_tokens,
                max_seq_len,
                recent_window,
                mgr_cfg,
                async_migration=ts.async_migration,
                ring_slots=ts.media_ring_slots,
                prefetch=ts.prefetch,
                prefetch_max_pages=ts.prefetch_max_pages,
                pool_bits={
                    "warm": getattr(ts, "warm_bits", 8),
                    "cold": getattr(ts, "cold_bits", 4),
                },
                host_media_device=getattr(ts, "host_media_device", ""),
                fault_plan=fault_plan,
            )
            # SSM side-state for hybrid archs.
            if cfg.family == "hybrid":
                s = cfg.ssm
                di = s.d_inner(cfg.d_model)
                cconv = di + 2 * s.n_groups * s.d_state
                self.ssm_state = (
                    jnp.zeros((cfg.n_layers, batch_slots, s.conv_kernel - 1, cconv),
                              jnp.bfloat16),
                    jnp.zeros(
                        (cfg.n_layers, batch_slots, s.n_heads(cfg.d_model), s.head_dim,
                         s.d_state),
                        jnp.float32,
                    ),
                )
            else:
                self.ssm_state = (jnp.zeros((0,)), jnp.zeros((0,)))
        self._prefill_fn = jax.jit(model.prefill)
        self._step_fn = jax.jit(
            serve_rt.make_tiered_decode_step(
                model, self.mesh, ParallelConfig(), ts, use_kernels=kops.compiled()
            )
        )

        # Program spans (``serving/spans.py``), shared with the cache.
        self.spans = self.cache.spans

        self.slots: List[Optional[Request]] = [None] * batch_slots
        self.slot_len = np.zeros(batch_slots, np.int64)
        self.queue: List[Request] = []
        self.stats = EngineStats()
        self._steps_in_window = 0
        # Monotonic request-id source: rids must stay unique for the whole
        # engine lifetime (frontend bookkeeping keys on them), so they can
        # never derive from the queue length.
        self._next_rid = 0

    # ----------------------------------------------------------------- API
    def make_request(self, prompt: np.ndarray, max_new_tokens: int,
                     tenant: int = 0) -> Request:
        """Mint a request with a unique monotonic rid WITHOUT enqueueing it
        (the frontend scheduler owns its own queue + slot placement)."""
        req = Request(rid=self._next_rid, prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=max_new_tokens, tenant=tenant)
        self._next_rid += 1
        return req

    def submit(self, prompt: np.ndarray, max_new_tokens: int, tenant: int = 0) -> Request:
        # recent_len/total_len are per-slot vectors in the tiered state, so
        # slots hold unequal prompt lengths and decode at their own
        # positions.
        req = self.make_request(prompt, max_new_tokens, tenant)
        self.queue.append(req)
        return req

    def try_submit(self, prompt: np.ndarray, max_new_tokens: int,
                   tenant: int = 0, budget_frac: float = 1.0) -> Optional[Request]:
        """Token-budget admission: enqueue only if the projected footprint
        (prompt + full generation) fits inside ``budget_frac`` of the device
        pools' token capacity alongside everything already outstanding.
        Returns None (refused) instead of overcommitting toward OOM."""
        projected = int(len(prompt)) + int(max_new_tokens)
        if self.outstanding_tokens() + projected > budget_frac * self.token_capacity():
            return None
        return self.submit(prompt, max_new_tokens, tenant)

    # ------------------------------------------------- headroom accounting
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def token_capacity(self) -> int:
        """Sequence-token capacity of the device pools plus the dense recent
        windows (a class row stores one page of ONE layer, so pool rows
        divide by the attention layer count)."""
        rows = self._alloc_capacity("warm") + self._alloc_capacity("cold")
        return (rows // self.la) * self.pt + self.bs * self.recent_window

    def _alloc_capacity(self, pool: str) -> int:
        return int(self.cache._alloc[pool].capacity)

    def device_headroom_tokens(self) -> int:
        """Live device-tier headroom in sequence tokens (free class rows
        across both pools, layer-divided) — the admission controller's
        immediate-placement signal."""
        free = len(self.cache._free_warm) + len(self.cache._free_cold)
        return (free // self.la) * self.pt

    def outstanding_tokens(self) -> int:
        """Tokens the engine is already committed to: resident context plus
        the ungenerated remainder of active requests, plus full projected
        footprints of everything still queued."""
        out = 0
        for i, req in enumerate(self.slots):
            if req is not None:
                out += int(self.slot_len[i])
                out += max(req.max_new_tokens - len(req.out_tokens), 0)
        for req in self.queue:
            out += len(req.prompt) + req.max_new_tokens
        return out

    # ------------------------------------------------------------ stepping
    @_on_device
    def compile_step(self):
        """Compile the decode step for this engine's shapes ahead of the
        first ``step`` — set-up time a caller can measure apart from serving
        (with the persistent compile cache on, ``step`` then loads it).
        Returns the ``jax.stages.Compiled`` executable; its ``as_text()``
        shows whether the fused kernel (``tpu_custom_call``) is in it."""
        tokens = jnp.zeros((self.bs, 1), jnp.int32)
        return self._step_fn.lower(
            self.params, tokens, self.cache.state, self.ssm_state
        ).compile()

    @_on_device
    def run(self, max_steps: int = 10_000) -> EngineStats:
        while (any(s is not None for s in self.slots) or self.queue) and self.stats.steps < max_steps:
            self._fill_slots()
            self.step()
        return self.finish()

    @_on_device
    def step(self) -> None:
        """One externally-drivable engine step: decode every active slot,
        then advance the profile window. The frontend scheduler calls this
        directly, interleaving placement/preemption between steps."""
        with self.spans.span("tkv.step"):
            self._decode_step()
            self._steps_in_window += 1
            if self._steps_in_window >= self.ts.window_steps:
                self._end_window()

    @_on_device
    def finish(self) -> EngineStats:
        """Drain in-flight cohorts and finalize the stats snapshot (idempotent
        — callable again after more stepping)."""
        with self.spans.span("tkv.finish"):
            self.cache.drain_migrations()
        self.stats.tco_savings_pct = max(
            self.stats.tco_savings_pct, self.cache.tco_savings_pct()
        )
        pipe = self.cache.pipeline
        self.stats.prefetch_staged = pipe.prefetch_staged
        self.stats.prefetch_hits = pipe.prefetch_hits
        self.stats.prefetch_misses = pipe.prefetch_misses
        self.stats.attn_launches = self.cache.attn_launches
        self.stats.attn_walk_slots = self.cache.attn_walk_slots
        self.stats.attn_pages = self.cache.attn_pages
        return self.stats

    # ----------------------------------------------- frontend slot control
    @_on_device
    def start_request(self, slot: int, req: Request) -> None:
        """Place ``req`` into a specific FREE slot and prefill it — the
        frontend's admission-controlled alternative to the internal queue
        (``_fill_slots``) path."""
        if self.slots[slot] is not None:
            raise ValueError(f"start_request: slot {slot} is occupied")
        self.cache.set_slot_tenant(slot, req.tenant)
        with self.spans.span("tkv.prefill", rid=req.rid):
            self._prefill(slot, req)
        self.slots[slot] = req

    @_on_device
    def preempt_slot(self, slot: int) -> PreemptedRequest:
        """Preemption-to-host-tier: demote the victim slot's device pages to
        their same-codec host tiers through the media pipeline (billed like
        normal demotions), park the payloads + recent window, and vacate the
        slot. The request keeps its pages — ``resume_into`` restores them
        with zero re-prefilled tokens."""
        req = self.slots[slot]
        if req is None or req.done:
            raise ValueError(f"preempt_slot: slot {slot} has no active request")
        with self.spans.span("tkv.preempt", rid=req.rid):
            levels = self.cache.demote_slot_to_host(slot)
            parked = self.cache.park_slot(slot, restore_levels=levels)
        pre = PreemptedRequest(request=req, parked=parked)
        if self.cfg.family == "hybrid":
            conv, sst = self.ssm_state
            pre.ssm_conv = np.asarray(conv[:, slot])
            pre.ssm_state = np.asarray(sst[:, slot])
        self.slots[slot] = None
        self.slot_len[slot] = 0
        self.stats.preemptions += 1
        return pre

    @_on_device
    def resume_into(self, slot: int, pre: PreemptedRequest) -> Request:
        """Swap a preempted request back into a free slot: parked host pages
        re-register and the previously device-resident ones ride swap-in
        cohorts home. No prompt token is ever recomputed."""
        if self.slots[slot] is not None:
            raise ValueError(f"resume_into: slot {slot} is occupied")
        with self.spans.span("tkv.resume", rid=pre.request.rid):
            restored = self.cache.restore_slot(slot, pre.parked)
        if self.cfg.family == "hybrid" and pre.ssm_conv is not None:
            conv, sst = self.ssm_state
            self.ssm_state = (
                conv.at[:, slot].set(jnp.asarray(pre.ssm_conv).astype(conv.dtype)),
                sst.at[:, slot].set(jnp.asarray(pre.ssm_state).astype(sst.dtype)),
            )
        self.slots[slot] = pre.request
        self.slot_len[slot] = pre.parked.total_len
        self.stats.resumes += 1
        self.stats.resumed_pages += restored
        return pre.request

    # ------------------------------------------------------------ internals
    def _fill_slots(self):
        for i in range(self.bs):
            if self.slots[i] is None and self.queue:
                req = self.queue.pop(0)
                self.start_request(i, req)

    def _prefill(self, slot: int, req: Request):
        """Dense prefill, then page the prompt KV into the warm tier
        (batched: one quant dispatch for all layers x pages)."""
        cfg = self.cfg
        s = len(req.prompt)
        if req.out_tokens:
            # An already-started request is being prefilled again — the
            # wasted recompute the preemption path exists to avoid.
            self.stats.re_prefill_tokens += s
        batch = {"tokens": jnp.asarray(req.prompt[None], jnp.int32)}
        with self.spans.span("tkv.prefill.compute", rid=req.rid):
            state = self.model.init_cache(1, max(s + 1, self.pt))
            logits, state = self._prefill_fn(self.params, batch, state)
            k = np.asarray(state.k_cache.astype(jnp.float32))  # [L,1,S,KV,hd]
            v = np.asarray(state.v_cache.astype(jnp.float32))
        # Page out everything except the tail that fits the recent window.
        n_full_pages = max((s - self.recent_window // 2) // self.pt, 0)
        entries = [
            (layer, slot, page)
            for layer in range(self.la) for page in range(n_full_pages)
        ]
        if entries:
            kp = np.stack([k[layer, 0, page * self.pt:(page + 1) * self.pt]
                           for layer, _, page in entries])
            vp = np.stack([v[layer, 0, page * self.pt:(page + 1) * self.pt]
                           for layer, _, page in entries])
            with self.spans.span("tkv.prefill.page_in", rid=req.rid):
                self.cache.append_pages(entries, jnp.asarray(kp), jnp.asarray(vp))
        # Remaining tail into the recent window.
        tail = slice(n_full_pages * self.pt, s)
        tlen = s - n_full_pages * self.pt
        st = self.cache.state
        rk = st.recent_k.at[:, slot, :tlen].set(
            jnp.asarray(k[:, 0, tail]).astype(st.recent_k.dtype))
        rv = st.recent_v.at[:, slot, :tlen].set(
            jnp.asarray(v[:, 0, tail]).astype(st.recent_v.dtype))
        self.cache.state = dataclasses.replace(
            st, recent_k=rk, recent_v=rv,
            recent_len=st.recent_len.at[slot].set(tlen),
            total_len=st.total_len.at[slot].set(s),
        )
        self.slot_len[slot] = s
        req.out_tokens.append(int(jnp.argmax(logits[0, -1])))

        if cfg.family == "hybrid":
            # Recompute SSM states for this slot via recurrent prefill.
            dstate = self.model.init_cache(1, s + 1)
            dstate = self.model._prefill_recurrent(
                self.params, batch, dstate,
                serve_rt.shr.activation_sharding(self.mesh, ParallelConfig()))
            conv, sst = self.ssm_state
            self.ssm_state = (
                conv.at[:, slot].set(dstate.conv_state[:, 0].astype(conv.dtype)),
                sst.at[:, slot].set(dstate.ssm_state[:, 0]),
            )

    def _decode_step(self):
        span = self.spans.span
        with span("tkv.dispatch"):
            tokens = np.zeros((self.bs, 1), np.int32)
            for i, req in enumerate(self.slots):
                if req is not None and req.out_tokens:
                    tokens[i, 0] = req.out_tokens[-1]
            logits, tkv, ssm_state, telemetry = self._step_fn(
                self.params, jnp.asarray(tokens), self.cache.state, self.ssm_state
            )
        self.cache.state = tkv
        self.ssm_state = ssm_state
        # The telemetry fold below reads the step's outputs first; waiting
        # for them here puts that wait in a span of its own, no earlier.
        with span("tkv.wait"):
            jax.block_until_ready((logits, telemetry))
        self.cache.record_telemetry(telemetry)
        # Advance in-flight migration cohorts by one phase: decode retired a
        # step while migration ran — the overlap the async pipeline buys.
        if self.cache.pipeline.busy:
            with span("tkv.pipeline"):
                self.cache.pipeline.tick()
            self.stats.overlapped_steps += 1
        else:
            # Idle media path: spend the step on speculative prefetch of
            # warming host pages (no-op unless ts.prefetch enabled).
            with span("tkv.prefetch"):
                self.cache.prefetch_tick()

        with span("tkv.sample"):
            next_tok = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
            for i, req in enumerate(self.slots):
                if req is None:
                    continue
                req.out_tokens.append(int(next_tok[i]))
                self.slot_len[i] += 1
                if len(req.out_tokens) >= req.max_new_tokens:
                    req.done = True
                    self.stats.completed += 1
                    self.stats.completed_by_tenant[req.tenant] = (
                        self.stats.completed_by_tenant.get(req.tenant, 0) + 1
                    )
                    self._release_slot(i)
        self.stats.steps += 1
        self._maybe_page_out_recent()

    def _maybe_page_out_recent(self):
        """When a slot's recent window fills, compress its oldest full
        pages. Per-slot: each slot pages out at its own fill level and its
        recent rows shift by its own amount (slots hold unequal lengths)."""
        st = self.cache.state
        rl = np.asarray(st.recent_len)  # [B]
        full = [
            i for i, req in enumerate(self.slots)
            if req is not None and int(rl[i]) >= self.recent_window
        ]
        if not full:
            return
        with self.spans.span("tkv.page_out"):
            k = np.asarray(st.recent_k.astype(jnp.float32))  # [L,B,R,KV,hd]
            v = np.asarray(st.recent_v.astype(jnp.float32))
            # Page out all layers x full-slots x pages in one batched append.
            entries, kps, vps = [], [], []
            shift = np.zeros(self.bs, np.int64)
            for i in full:
                # Move floor(rl/pt)-1 pages out, keep the newest tokens dense
                # (n_out >= 1: the window is full, something must leave).
                n_out = max(int(rl[i]) // self.pt - 1, 1)
                shift[i] = n_out * self.pt
            for layer in range(self.la):
                for i in full:
                    start_tok = int(self.slot_len[i]) - int(rl[i])
                    for p in range(int(shift[i]) // self.pt):
                        page_idx = (start_tok + p * self.pt) // self.pt
                        sl = slice(p * self.pt, (p + 1) * self.pt)
                        entries.append((layer, i, page_idx))
                        kps.append(k[layer, i, sl])
                        vps.append(v[layer, i, sl])
            if entries:
                self.cache.append_pages(
                    entries, jnp.asarray(np.stack(kps)), jnp.asarray(np.stack(vps))
                )
            st = self.cache.state
            # Per-slot roll, device-side: row b reads from (j + shift[b]) % R.
            r = st.recent_k.shape[2]
            idx = (jnp.arange(r, dtype=jnp.int32)[None, :]
                   + jnp.asarray(shift, jnp.int32)[:, None]) % r  # [B, R]
            gidx = idx[None, :, :, None, None]
            self.cache.state = dataclasses.replace(
                st,
                recent_k=jnp.take_along_axis(st.recent_k, gidx, axis=2),
                recent_v=jnp.take_along_axis(st.recent_v, gidx, axis=2),
                recent_len=st.recent_len - jnp.asarray(shift, jnp.int32),
            )

    def _release_slot(self, slot: int):
        """Request finished: free its pages everywhere (batched)."""
        self.cache.release_slot_pages(slot)
        self.slots[slot] = None
        self.slot_len[slot] = 0
        st = self.cache.state
        self.cache.state = dataclasses.replace(
            st,
            recent_len=st.recent_len.at[slot].set(0),
            total_len=st.total_len.at[slot].set(0),
        )

    def _end_window(self):
        with self.spans.span("tkv.end_window"):
            plan, moved = self.cache.end_window()
            self.stats.migrations += moved
            self.stats.windows += 1
            self._steps_in_window = 0
            # Snapshot TCO savings while pages are live (completion frees them).
            self.stats.tco_savings_pct = max(
                self.stats.tco_savings_pct, self.cache.tco_savings_pct()
            )
            for t in {r.tenant for r in self.slots if r is not None}:
                self.stats.tco_savings_by_tenant[t] = max(
                    self.stats.tco_savings_by_tenant.get(t, 0.0),
                    self.cache.tco_savings_pct(tenant=t),
                )
