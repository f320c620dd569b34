"""Serve-step builders: prefill and decode (dense KV cache or tiered
compressed KV pools), with the shardings the dry-run lowers against.

``decode`` lowers one engine step: append one token per sequence against a
seq_len-long KV cache — the ``decode_32k`` / ``long_500k`` cells.

``make_tiered_decode_step`` is the paper's technique on the decode path:
the KV cache's warm/cold pages live in two device-resident quantized pools
(host tiers are engine-managed outside the step, visible only as sentinel
rows); attention runs as ONE fused pass over all pools + host sentinels +
the dense recent window (the megakernel with ``use_kernels=True``, its
jnp oracle otherwise). Per-page softmax mass — including the host pages'
would-have-touched mass — comes back as telemetry for the TierScape
manager and its prefetch predictor.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig, TierScapeRunConfig
from repro.models import layers
from repro.models.transformer import DecodeState, Model
from repro.runtime import sharding as shr

PyTree = Any


@dataclasses.dataclass
class ServeStep:
    fn: Callable
    params_specs: PyTree
    state_specs: PyTree
    token_spec: PyTree
    mesh: Mesh


def make_decode_step(
    model: Model, mesh: Mesh, parallel: ParallelConfig,
    batch_size: int = 1, max_len: int = 1024,
) -> ServeStep:
    cfg = model.cfg
    act_shard = shr.activation_sharding(mesh, parallel, batch_size)

    def step(params, token, state: DecodeState):
        logits, state = model.decode_step(params, token, state, shard=act_shard)
        return logits, state

    params_shape = jax.eval_shape(lambda k: model.init(k), jax.random.PRNGKey(0))
    p_specs = shr.param_specs(params_shape, cfg, mesh, parallel)
    s_specs = shr.decode_state_specs(cfg, mesh, parallel, batch_size, max_len)
    bax = shr.bax_spec(mesh, batch_size)
    return ServeStep(
        fn=step,
        params_specs=p_specs,
        state_specs=s_specs,
        token_spec=P(bax, None),
        mesh=mesh,
    )


def make_prefill_step(model: Model, mesh: Mesh, parallel: ParallelConfig):
    cfg = model.cfg
    act_shard = shr.activation_sharding(mesh, parallel)

    def step(params, batch):
        logits, aux = model.forward(params, batch, shard=act_shard)
        return logits[:, -1]

    params_shape = jax.eval_shape(lambda k: model.init(k), jax.random.PRNGKey(0))
    p_specs = shr.param_specs(params_shape, cfg, mesh, parallel)
    return step, p_specs


# ---------------------------------------------------------------------------
# Tiered decode (the paper's technique on the serving path)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TieredKVState:
    """Device-resident tiered KV state for the jitted decode step.

    Payload storage is CODEC-CLASS-MAJOR: one shared int8-class buffer
    (``c8_*``) and one int4-class buffer (``c4_*``), each holding the rows
    of EVERY tier pool of that codec width and of every layer (a row holds
    one layer's page; rows are allocated across layers, so the buffers
    carry no layer axis and every layer's kernel addresses the whole
    buffer without a per-layer slice). Per-pool page tables
    (``warm_table``/``cold_table``) stay, but their entries are GLOBAL rows
    of the pool's class buffer (``SlotAllocator`` row ranges carve the
    buffer up per pool) — so N same-class tiers address one buffer with
    zero per-step payload concatenation in the fused kernel, and same-class
    migrations are pure table edits. With the default warm=int8/cold=int4
    split each class holds exactly one pool and the layout degenerates to
    the former per-pool buffers (bit-identical shapes and addressing).

    Host tiers (C2/C4/C12) hold evicted pages outside the step; the engine
    swaps them through the warm pool. Host-resident pages are still
    *visible* to the step as sentinel rows: a tiny per-page key centroid
    (``host_summary``) + a sentinel table, which the fused attention launch
    scores for would-have-touched hotness telemetry without fetching any
    payload.
    """

    c8_k: jax.Array  # [P8, T, KV, hd] int8 — shared int8-class rows
    c8_k_scales: jax.Array  # [P8, T, KV] f32
    c8_v: jax.Array
    c8_v_scales: jax.Array
    c4_k: jax.Array  # [P4, T, KV, hd//2] uint8 — shared int4-class rows
    c4_k_scales: jax.Array
    c4_v: jax.Array
    c4_v_scales: jax.Array
    warm_table: jax.Array  # [L, B, MPw] int32 — global class-buffer rows
    warm_n: jax.Array  # [L, B] int32
    cold_table: jax.Array
    cold_n: jax.Array
    recent_k: jax.Array  # [L, B, R, KV, hd] bf16
    recent_v: jax.Array
    recent_len: jax.Array  # [B] int32 — per-slot dense-window fill
    total_len: jax.Array  # [B] int32 — per-slot sequence position
    host_summary: jax.Array  # [L, Hs, KV, hd] f32 — host-page key centroids
    host_table: jax.Array  # [L, B, MP] int32 — sentinel rows -> summary slot
    host_n: jax.Array  # [L, B] int32


# Class-buffer payload fields (``c8_k``, ``c4_v_scales``, ...) and the
# fields that carry a leading attention-layer axis.
CLASS_FIELDS = ("k", "k_scales", "v", "v_scales")
PER_LAYER_FIELDS = (
    "warm_table", "warm_n", "cold_table", "cold_n", "recent_k", "recent_v",
    "host_summary", "host_table", "host_n",
)


def class_rows_of(
    warm_pages: int, cold_pages: int, warm_bits: int = 8, cold_bits: int = 4
) -> Dict[int, int]:
    """Rows per codec-class buffer for the (warm, cold) pool pair, warm
    range first (the ``ClassPartition`` order the cache's allocators use).
    An empty class keeps one dummy row so the kernel operands stay
    non-degenerate; ``TIER_INVALID`` masking guarantees it is never read."""
    rows = {8: 0, 4: 0}
    rows[warm_bits] += warm_pages
    rows[cold_bits] += cold_pages
    return {b: max(r, 1) for b, r in rows.items()}


def init_tiered_kv_state(
    cfg: ModelConfig,
    batch: int,
    *,
    page_tokens: int,
    warm_pages: int,
    cold_pages: int,
    max_pages_per_seq: int,
    recent_window: int,
    n_attn_layers: int,
    host_slots: Optional[int] = None,
    warm_bits: int = 8,
    cold_bits: int = 4,
) -> TieredKVState:
    hd = cfg.head_dim_()
    kv = cfg.n_kv_heads
    la = n_attn_layers
    t = page_tokens
    hs = max(host_slots if host_slots is not None else cold_pages, 1)
    rows = class_rows_of(warm_pages, cold_pages, warm_bits, cold_bits)
    p8, p4 = rows[8], rows[4]
    return TieredKVState(
        c8_k=jnp.zeros((p8, t, kv, hd), jnp.int8),
        c8_k_scales=jnp.ones((p8, t, kv), jnp.float32),
        c8_v=jnp.zeros((p8, t, kv, hd), jnp.int8),
        c8_v_scales=jnp.ones((p8, t, kv), jnp.float32),
        c4_k=jnp.zeros((p4, t, kv, hd // 2), jnp.uint8),
        c4_k_scales=jnp.ones((p4, t, kv), jnp.float32),
        c4_v=jnp.zeros((p4, t, kv, hd // 2), jnp.uint8),
        c4_v_scales=jnp.ones((p4, t, kv), jnp.float32),
        warm_table=jnp.zeros((la, batch, max_pages_per_seq), jnp.int32),
        warm_n=jnp.zeros((la, batch), jnp.int32),
        cold_table=jnp.zeros((la, batch, max_pages_per_seq), jnp.int32),
        cold_n=jnp.zeros((la, batch), jnp.int32),
        recent_k=jnp.zeros((la, batch, recent_window, kv, hd), jnp.bfloat16),
        recent_v=jnp.zeros((la, batch, recent_window, kv, hd), jnp.bfloat16),
        recent_len=jnp.zeros((batch,), jnp.int32),
        total_len=jnp.zeros((batch,), jnp.int32),
        host_summary=jnp.zeros((la, hs, kv, hd), jnp.float32),
        host_table=jnp.zeros((la, batch, max_pages_per_seq), jnp.int32),
        host_n=jnp.zeros((la, batch), jnp.int32),
    )


def make_sp_pool_attention(mesh: Mesh, batch_axes: Tuple[str, ...]):
    """Sequence/batch-parallel tiered-pool attention via shard_map.

    Pools shard on the PAGE dim over (batch axes x model): the engine owns
    allocation, placing a sequence's pages on the (pod, data) shard that owns
    the sequence, striped over ``model`` by table slot — so every gather is
    local. Tables shard (batch over data axes, slots over model); each shard
    computes flash partials over its local pages; partials merge with an
    exact logsumexp psum over ``model`` only. Compute, pool HBM and gather
    traffic all divide by the full mesh — the SPMD-auto path instead
    all-gathers the entire dequantized pool (the baseline bottleneck).
    """
    page_axes: Tuple[str, ...] = tuple(batch_axes) + ("model",)
    page_spec = page_axes if len(page_axes) > 1 else page_axes[0]
    bax = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)

    def partial_fn(q, kp, ks, vp, vs, table, slot_pos, n_pages, bits):
        from repro.kernels import ref as kref

        # Local page ids: global ids striped over every pool shard.
        nshards = 1
        for a in page_axes:
            nshards *= jax.lax.psum(1, a)
        local_table = table // nshards
        out_u, m, l, mass, base = kref.paged_quant_attention(
            q, kp, ks, vp, vs, local_table, n_pages, bits, slot_pos=slot_pos
        )
        # Exact cross-shard logsumexp merge over the slot axis.
        m_tot = jax.lax.pmax(m, "model")
        w = jnp.exp(m - m_tot)
        out_m = jax.lax.psum(out_u * w[..., None], "model")
        l_m = jax.lax.psum(l * w, "model")
        return out_m, m_tot, l_m, mass, base

    def run(q, pool, bits):
        mp = pool["page_table"].shape[1]
        b = pool["page_table"].shape[0]
        slot_pos = jnp.broadcast_to(jnp.arange(mp, dtype=jnp.int32)[None], (b, mp))
        fn = jax.shard_map(
            lambda *a: partial_fn(*a, bits=bits),
            mesh=mesh,
            in_specs=(
                P(bax, None, None),  # q: one token per sequence
                P(page_spec, None, None, None),
                P(page_spec, None, None),
                P(page_spec, None, None, None),
                P(page_spec, None, None),
                P(bax, "model"),  # table: batch rows + slots sharded
                P(bax, "model"),  # global slot positions
                P(bax),  # n_pages per batch row
            ),
            out_specs=(
                P(bax, None, None),  # merged out_u
                P(bax, None),  # merged m
                P(bax, None),  # merged l
                P(bax, "model"),  # local masses stay slot-sharded
                P(bax, "model"),
            ),
            check_vma=False,
        )
        return fn(q, pool["k_pages"], pool["k_scales"], pool["v_pages"],
                  pool["v_scales"], pool["page_table"], slot_pos, pool["n_pages"])

    return run


def make_tiered_decode_step(
    model: Model,
    mesh: Mesh,
    parallel: ParallelConfig,
    ts_cfg: TierScapeRunConfig,
    use_kernels: bool = False,
):
    """Decode step over tiered KV pools for attention/hybrid archs.

    Returns (step_fn, specs...). step_fn(params, token, tkv, extra_state)
    -> (logits, tkv, extra_state, telemetry) where extra_state carries the
    SSM states for hybrid archs (None-sized otherwise) and telemetry is the
    per-layer warm/cold page attention mass.
    """
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref
    from repro.models import attention as attn_mod
    from repro.models import mlp as mlp_mod
    from repro.models import ssm as ssm_mod

    cfg = model.cfg
    act_shard = shr.activation_sharding(mesh, parallel)
    tp = shr.axis_size(mesh, "model")
    # Sequence-parallel pool attention (shard_map): pages, tables, compute
    # and gathers all divide by TP. Requires the engine's slot-striped page
    # allocation (table column j holds pages of shard j*TP//MP).
    use_sp = parallel.shard_kv_seq and tp > 1 and not use_kernels
    sp_attn = None
    _batch_axes_holder = []
    # Device-pool codec widths (class-major: a pool's payload lives in its
    # class's shared buffer). Defaults give the classic warm=int8/cold=int4
    # split; same-width pairs share one buffer with zero per-step copies.
    wb = int(getattr(ts_cfg, "warm_bits", 8))
    cb = int(getattr(ts_cfg, "cold_bits", 4))
    warm_cls = "c8" if wb == 8 else "c4"
    cold_cls = "c8" if cb == 8 else "c4"

    def _make_sp(batch_size):
        return make_sp_pool_attention(mesh, shr.batch_axes_for(mesh, batch_size))

    def attend_tiered(blk, x, layer_tkv, total_len, recent_len):
        """x [B,1,D]; one attention layer against pools + recent window.
        ``total_len``/``recent_len`` are per-slot [B] vectors: each slot
        rotary-encodes at its own position and appends the new token at its
        own dense-window offset (slots hold unequal sequence lengths)."""
        hn = layers.apply_norm(cfg.norm, blk["norm1"], x, cfg.norm_eps)
        b = x.shape[0]
        positions = total_len[:, None].astype(jnp.int32)  # [B, 1]
        q, k_new, v_new = attn_mod._project_qkv(blk["attn"], cfg, hn, positions, act_shard)
        # Per-slot scatter at index recent_len[b]: one-hot masked write (the
        # vector analogue of dynamic_update_slice_in_dim; an index beyond
        # the window writes nothing, matching an inactive slot).
        r = layer_tkv["recent_k"].shape[1]
        at = (jnp.arange(r, dtype=jnp.int32)[None, :] == recent_len[:, None])
        at = at[:, :, None, None]  # [B, R, 1, 1]
        recent_k = jnp.where(
            at, k_new.astype(layer_tkv["recent_k"].dtype), layer_tkv["recent_k"]
        )
        recent_v = jnp.where(
            at, v_new.astype(layer_tkv["recent_v"].dtype), layer_tkv["recent_v"]
        )
        # Class-major pools: each pool's payload arrays ARE its codec
        # class's shared buffer (same jax array object when two pools share
        # a class — the zero-concat contract ``ops._unified_operands``
        # detects by identity); tables hold global class-buffer rows.
        def pool_of(cls, table, n, bits):
            return {
                "k_pages": layer_tkv[f"{cls}_k"],
                "k_scales": layer_tkv[f"{cls}_k_scales"],
                "v_pages": layer_tkv[f"{cls}_v"],
                "v_scales": layer_tkv[f"{cls}_v_scales"],
                "page_table": layer_tkv[table],
                "n_pages": layer_tkv[n],
                "bits": bits,
            }

        pools = {
            "warm": pool_of(warm_cls, "warm_table", "warm_n", wb),
            "cold": pool_of(cold_cls, "cold_table", "cold_n", cb),
        }
        # Host sentinel rows ride the same attention pass: no payload, just
        # the per-page key centroid scored for would-have-touched mass.
        host = {
            "summary": layer_tkv["host_summary"],
            "table": layer_tkv["host_table"],
            "n": layer_tkv["host_n"],
            "page_tokens": layer_tkv[f"{warm_cls}_k"].shape[1],
        }
        if use_kernels:
            # Fused megakernel: ONE Pallas launch for all pools + host
            # sentinels + the recent window (see kernels/ops.py).
            # Each table is one sequence's max_pages wide, and a sequence's
            # pages split across warm, cold and host never exceed that: the
            # kernel walks at most that many pages per slot.
            out, hot = kops.tiered_decode_attention(
                q[:, 0], pools, recent_k, recent_v, recent_len + 1, cfg,
                with_telemetry=True, host=host,
                max_pages=layer_tkv["warm_table"].shape[-1],
            )
        elif use_sp:
            sp = _make_sp(b)
            parts = [kref.dense_recent_attention(q[:, 0], recent_k, recent_v, recent_len + 1)]
            hot = {}
            for name in ("warm", "cold"):
                out_u, m, l, mass, _base = sp(q[:, 0], pools[name], pools[name]["bits"])
                parts.append((out_u, m, l))
                hot[name] = mass  # unnormalized local masses (telemetry)
            hot["host"], _ = kref.host_page_mass(
                q[:, 0], host["summary"], host["table"], host["n"], host["page_tokens"]
            )
            out = kref.merge_partials(parts)
        else:
            # Pure-jnp fused oracle: same semantics as the megakernel
            # (exact merge + live telemetry incl. host mass), XLA-fused.
            out, m_tot, l_tot, masses = kref.fused_tiered_attention(
                q[:, 0], pools, recent_k, recent_v, recent_len + 1, host=host
            )
            hot = {
                name: kops.page_hotness(mass, base, m_tot, l_tot)
                for name, (mass, base) in masses.items()
            }
        y = jnp.einsum("bhk,hkd->bd", out.astype(x.dtype), blk["attn"]["wo"])[:, None]
        if cfg.attn_out_bias:
            y = y + blk["attn"]["bo"]
        return x + y, recent_k, recent_v, hot

    def layer_view(tkv: TieredKVState, li: int) -> Dict[str, jax.Array]:
        """One attention layer's slice of the state. The class buffers are
        not sliced: their rows are global across layers."""
        view = {f: getattr(tkv, f)[li] for f in PER_LAYER_FIELDS}
        view.update({f"{c}_{f}": getattr(tkv, f"{c}_{f}")
                     for c in ("c8", "c4") for f in CLASS_FIELDS})
        return view

    def step(params, token, tkv: TieredKVState, ssm_state):
        x = params["embed"][token]
        recent_len = tkv.recent_len
        total_len = tkv.total_len
        telemetry = {"warm": [], "cold": [], "host": []}

        new_recent_k, new_recent_v = [], []
        if cfg.family == "hybrid":
            every = cfg.hybrid_attn_every
            n_apps = tkv.recent_k.shape[0]
            conv_states, ssm_states = ssm_state
            new_conv, new_ssm = [], []

            def ssm_body(h, layer):
                blk, conv, sst = layer
                hn = layers.apply_norm(cfg.norm, blk["norm"], h, cfg.norm_eps)
                y, conv, sst = ssm_mod.ssm_decode_step(blk["mixer"], cfg, hn, conv, sst)
                return h + y, (conv, sst)

            done = 0
            for g in range(n_apps):
                layer_tkv = layer_view(tkv, g)
                x, rk, rv, hot = attend_tiered(params["shared"], x, layer_tkv, total_len, recent_len)
                hn = layers.apply_norm(cfg.norm, params["shared"]["norm2"], x, cfg.norm_eps)
                x = x + mlp_mod.mlp(params["shared"]["ffn"], cfg, hn)
                new_recent_k.append(rk)
                new_recent_v.append(rv)
                telemetry["warm"].append(hot["warm"])
                telemetry["cold"].append(hot["cold"])
                telemetry["host"].append(hot["host"])

                width = min(every, cfg.n_layers - done)
                group = jax.tree.map(lambda a: a[done : done + width], params["blocks"])
                x, (cv, ss) = jax.lax.scan(
                    ssm_body, x, (group, conv_states[done : done + width], ssm_states[done : done + width])
                )
                new_conv.append(cv)
                new_ssm.append(ss)
                done += width
            ssm_state = (jnp.concatenate(new_conv), jnp.concatenate(new_ssm))
        else:
            n_layers = tkv.recent_k.shape[0]
            for li in range(n_layers):
                blk = jax.tree.map(lambda a: a[li], params["blocks"])
                layer_tkv = layer_view(tkv, li)
                x, rk, rv, hot = attend_tiered(blk, x, layer_tkv, total_len, recent_len)
                hn = layers.apply_norm(cfg.norm, blk["norm2"], x, cfg.norm_eps)
                if cfg.family == "moe":
                    y2, _ = moe_ffn_local(blk, x, hn)
                else:
                    y2 = mlp_mod.mlp(blk["ffn"], cfg, hn)
                x = x + y2
                new_recent_k.append(rk)
                new_recent_v.append(rv)
                telemetry["warm"].append(hot["warm"])
                telemetry["cold"].append(hot["cold"])
                telemetry["host"].append(hot["host"])

        tkv = dataclasses.replace(
            tkv,
            recent_k=jnp.stack(new_recent_k),
            recent_v=jnp.stack(new_recent_v),
            recent_len=recent_len + 1,
            total_len=total_len + 1,
        )
        x = layers.apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
        logits = model._head(params, x)
        telemetry = {k: jnp.stack(v) for k, v in telemetry.items()}
        return logits, tkv, ssm_state, telemetry

    def moe_ffn_local(blk, x, hn):
        from repro.models import moe as moe_mod

        return moe_mod.moe_ffn(blk["moe"], cfg, hn)

    return step


def tiered_kv_state_specs(
    mesh: Mesh, parallel: ParallelConfig, batch_size: int = 1, n_pool_pages: int = 0
) -> TieredKVState:
    """Pool pages shard over the model axis (sequence-parallel KV: each model
    shard owns a slice of every sequence's pages); batch dims over data."""
    bax = shr.bax_spec(mesh, batch_size)
    tp = shr.axis_size(mesh, "model")
    axes = shr.batch_axes_for(mesh, batch_size) + ("model",)
    n_shards = 1
    for a in axes:
        n_shards *= shr.axis_size(mesh, a)
    sp_on = parallel.shard_kv_seq and tp > 1 and n_pool_pages and n_pool_pages % n_shards == 0
    page_ax = (axes if len(axes) > 1 else axes[0]) if sp_on else None
    # Table slots shard with the pages (sequence parallelism).
    table_ax = "model" if sp_on else None
    return TieredKVState(
        c8_k=P(page_ax, None, None, None),
        c8_k_scales=P(page_ax, None, None),
        c8_v=P(page_ax, None, None, None),
        c8_v_scales=P(page_ax, None, None),
        c4_k=P(page_ax, None, None, None),
        c4_k_scales=P(page_ax, None, None),
        c4_v=P(page_ax, None, None, None),
        c4_v_scales=P(page_ax, None, None),
        warm_table=P(None, bax, table_ax),
        warm_n=P(None, bax),
        cold_table=P(None, bax, table_ax),
        cold_n=P(None, bax),
        recent_k=P(None, bax, None, None, None),
        recent_v=P(None, bax, None, None, None),
        recent_len=P(bax),
        total_len=P(bax),
        # Host sentinel summaries are tiny (one [KV, hd] vector per page);
        # replicate them like the tables so sentinel gathers stay local.
        host_summary=P(None, None, None, None),
        host_table=P(None, bax, None),
        host_n=P(None, bax),
    )
