"""Cell construction: one (architecture x input-shape) combination -> the
step function + abstract inputs + shardings the dry-run lowers.

Cell kinds:
  train_4k    -> train_step   (loss+grad+AdamW; fsdp per size heuristic,
                               microbatch grad accumulation)
  prefill_32k -> prefill_step (full forward, chunked attention)
  decode_32k  -> serve_step   (one token vs a seq_len dense KV cache,
                               sequence-parallel KV sharding)
  long_500k   -> serve_step   (SSM: recurrent state; hybrid: TIERED
                               compressed KV pools — the paper's technique
                               in the lowered artifact)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

import repro.configs as configs
from repro.configs.base import ModelConfig, ParallelConfig, SHAPES, TierScapeRunConfig
from repro.models import inputs as minputs
from repro.models.transformer import Model, _attn_layer_count
from repro.optim import adamw, tiered_adam
from repro.runtime import serve as serve_rt
from repro.runtime import sharding as shr
from repro.runtime import train as train_rt

PyTree = Any


@dataclasses.dataclass
class Cell:
    arch: str
    shape: str
    kind: str
    fn: Callable
    in_shardings: Tuple
    abstract_args: Tuple
    mesh: Mesh
    notes: str = ""
    donate: Tuple[int, ...] = ()
    # Pinning outputs to the input shardings keeps donation/aliasing intact
    # (otherwise XLA may pick a different output layout and materialize a
    # full copy of donated state, e.g. a 32k KV cache).
    out_shardings: Any = None

    def lower(self):
        kw = {}
        if self.out_shardings is not None:
            kw["out_shardings"] = self.out_shardings
        with self.mesh:
            return jax.jit(
                self.fn, in_shardings=self.in_shardings, donate_argnums=self.donate, **kw
            ).lower(*self.abstract_args)


def _sds(tree: PyTree) -> PyTree:
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _shardings(mesh: Mesh, specs: PyTree) -> PyTree:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )


def _dp(mesh: Mesh) -> int:
    return shr.axis_size(mesh, "data") * shr.axis_size(mesh, "pod")


def default_parallel(cfg: ModelConfig, shape_name: str, mesh: Mesh) -> ParallelConfig:
    kind = SHAPES[shape_name].kind
    params_gb = cfg.param_count() * 2 / 1024**3
    tp = shr.axis_size(mesh, "model")
    if kind == "train":
        # Training: params + f32 moments resident -> FSDP early.
        fsdp = params_gb / max(tp, 1) > 2.0
    else:
        # Inference: only bf16 params resident; FSDP would re-gather params
        # every decode token — avoid unless TP alone can't fit them.
        fsdp = params_gb / max(tp, 1) > 8.0
    accum = 1
    if kind == "train":
        sh = SHAPES[shape_name]
        local_batch = max(sh.global_batch // _dp(mesh), 1)
        # Per-microbatch activation budget, tuned per family: SSD's chunk
        # tensors (f32 [B,nc,H,ch,ch]) and MoE's dispatch buffers blow up
        # much faster per token than a dense residual stream.
        target_mb = {"ssm": 16, "hybrid": 16, "moe": 64, "vlm": 64}.get(cfg.family, 128)
        per_seq_bytes = sh.seq_len * max(cfg.d_model, 1) * 2
        micro = max(int((target_mb << 20) // per_seq_bytes), 1)
        while local_batch % micro and micro > 1:
            micro -= 1
        accum = max(local_batch // micro, 1)
    return ParallelConfig(
        fsdp=fsdp,
        grad_accum=accum,
        shard_kv_seq=(kind == "decode" and cfg.has_attention),
    )


def moe_tiered_policy(params_shape) -> dict:
    """MoE train cells store moments through compressed tiers (embeddings &
    expert weights int8) — paper technique applied to training state, and
    what makes the 235B fit the pod."""
    policy = {}

    def visit(path, leaf):
        p = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        if "embed" in p or "lm_head" in p or "/moe/w_" in p:
            policy[p] = "int8"
        else:
            policy[p] = "none"

    jax.tree_util.tree_map_with_path(visit, params_shape)
    return policy


def build_cell(
    arch: str,
    shape_name: str,
    mesh: Mesh,
    parallel: Optional[ParallelConfig] = None,
    smoke: bool = False,
    tiered_kv: Optional[bool] = None,
    page_tokens: int = 64,
    warm_frac: float = 0.125,
) -> Cell:
    cfg = configs.get_smoke(arch) if smoke else configs.get(arch)
    shape = SHAPES[shape_name]
    parallel = parallel or default_parallel(cfg, shape_name, mesh)
    model = Model(cfg, parallel)
    notes = f"fsdp={parallel.fsdp} accum={parallel.grad_accum} kvseq={parallel.shard_kv_seq}"

    params_shape = jax.eval_shape(lambda k: model.init(k), jax.random.PRNGKey(0))
    p_specs = shr.param_specs(params_shape, cfg, mesh, parallel)
    p_shard = _shardings(mesh, p_specs)

    if shape.kind == "train":
        batch_sds = minputs.train_batch_spec(cfg, shape.global_batch, shape.seq_len)
        tiered_policy = moe_tiered_policy(params_shape) if cfg.family == "moe" else None
        step = train_rt.make_train_step(
            model, adamw.AdamWConfig(), mesh, parallel, batch_sds, tiered_policy
        )
        if tiered_policy is not None:
            opt_sds = jax.eval_shape(lambda p: tiered_adam.init(p, tiered_policy), params_shape)
        else:
            opt_sds = jax.eval_shape(adamw.init, params_shape)
        args = (params_shape, opt_sds, batch_sds)
        in_sh = (
            p_shard,
            _shardings(mesh, step.opt_specs),
            _shardings(mesh, step.batch_specs),
        )
        out_sh = (in_sh[0], in_sh[1], None)
        return Cell(arch, shape_name, "train", step.fn, in_sh, args, mesh, notes,
                    donate=(0, 1), out_shardings=out_sh)

    if shape.kind == "prefill":
        batch_sds = minputs.train_batch_spec(cfg, shape.global_batch, shape.seq_len)
        batch_sds.pop("targets", None)
        batch_sds.pop("loss_mask", None)
        fn, _ = serve_rt.make_prefill_step(model, mesh, parallel)
        b_specs = shr.batch_spec(mesh, batch_sds)
        args = (params_shape, batch_sds)
        in_sh = (p_shard, _shardings(mesh, b_specs))
        return Cell(arch, shape_name, "prefill", fn, in_sh, args, mesh, notes)

    # ---- decode kinds -------------------------------------------------------
    assert cfg.is_decoder, f"{arch} has no decode step"
    use_tiered = tiered_kv if tiered_kv is not None else (
        shape_name == "long_500k" and cfg.has_attention
    )
    bsz = shape.global_batch

    if use_tiered:
        ts_cfg = TierScapeRunConfig(enabled=True)
        la = _attn_layer_count(cfg)
        n_pages = shape.seq_len // page_tokens
        # Class-buffer rows are global across attention layers.
        warm_pages = max(int(n_pages * warm_frac) * max(bsz, 1) * la, 8)
        cold_pages = max(n_pages * max(bsz, 1) * la, 8)
        tkv = jax.eval_shape(
            lambda: serve_rt.init_tiered_kv_state(
                cfg,
                bsz,
                page_tokens=page_tokens,
                warm_pages=warm_pages,
                cold_pages=cold_pages,
                max_pages_per_seq=n_pages,
                recent_window=256,
                n_attn_layers=la,
            )
        )
        if cfg.family == "hybrid":
            s = cfg.ssm
            di = s.d_inner(cfg.d_model)
            cconv = di + 2 * s.n_groups * s.d_state
            ssm_sds = (
                jax.ShapeDtypeStruct((cfg.n_layers, bsz, s.conv_kernel - 1, cconv), jnp.bfloat16),
                jax.ShapeDtypeStruct(
                    (cfg.n_layers, bsz, s.n_heads(cfg.d_model), s.head_dim, s.d_state),
                    jnp.float32,
                ),
            )
        else:
            ssm_sds = (
                jax.ShapeDtypeStruct((0,), jnp.float32),
                jax.ShapeDtypeStruct((0,), jnp.float32),
            )
        fn = serve_rt.make_tiered_decode_step(model, mesh, parallel, ts_cfg, use_kernels=False)
        tkv_specs = serve_rt.tiered_kv_state_specs(mesh, parallel, bsz, cold_pages)
        bax = shr.bax_spec(mesh, bsz)
        ssm_specs = (P(None, bax, None, None), P(None, bax, None, None, None)) if cfg.family == "hybrid" else (P(), P())
        tok = jax.ShapeDtypeStruct((bsz, 1), jnp.int32)
        args = (params_shape, tok, tkv, ssm_sds)
        in_sh = (
            p_shard,
            NamedSharding(mesh, P(bax, None)),
            _shardings(mesh, tkv_specs),
            _shardings(mesh, ssm_specs),
        )
        out_sh = (NamedSharding(mesh, P(bax, None, None)), in_sh[2], in_sh[3], None)
        return Cell(arch, shape_name, "tiered_decode", fn, in_sh, args, mesh,
                    notes + f" tiered_kv pages={n_pages} pt={page_tokens}",
                    donate=(2, 3), out_shardings=out_sh)

    # Dense-cache decode (or SSM-state decode). Cache length padded to a
    # multiple of TP so the kv-seq axis can shard.
    max_len = shape.seq_len + 64
    state_sds = jax.eval_shape(lambda: model.init_cache(bsz, max_len))
    s_specs = shr.decode_state_specs(cfg, mesh, parallel, bsz, max_len)
    act_shard = shr.activation_sharding(mesh, parallel, bsz)

    def step(params, token, state):
        return model.decode_step(params, token, state, shard=act_shard)

    bax = shr.bax_spec(mesh, bsz)
    tok = jax.ShapeDtypeStruct((bsz, 1), jnp.int32)
    args = (params_shape, tok, state_sds)
    in_sh = (p_shard, NamedSharding(mesh, P(bax, None)), _shardings(mesh, s_specs))
    out_sh = (NamedSharding(mesh, P(bax, None, None)), in_sh[2])
    return Cell(arch, shape_name, "decode", step, in_sh, args, mesh, notes,
                donate=(2,), out_shardings=out_sh)
