"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode.

Tolerances: dequantized values may differ by at most one quantization step
(jit reciprocal-multiply vs eager divide flips round-to-nearest ties); the
attention partials are compared at f32 accumulation tolerance.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.dequant_page import dequant_pages
from repro.kernels.paged_attention import paged_quant_attention
from repro.kernels.quant_page import quant_pages
from repro.kernels.transcode_page import transcode_pages

from proptest import cases, draw_choice, draw_log_float


def _pages(rng, p, t, kv, hd, dtype=jnp.bfloat16, scale=1.0):
    return jnp.asarray(rng.normal(0, scale, (p, t, kv, hd)), dtype)


SWEEP = [
    # (P, T, KV, HD)
    (4, 8, 1, 32),
    (4, 16, 4, 64),
    (8, 32, 2, 128),
    (2, 64, 8, 128),
]


@pytest.mark.parametrize("shape", SWEEP)
@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_quant_dequant_vs_ref(shape, bits, dtype):
    rng = np.random.default_rng(42)
    pages = _pages(rng, *shape, dtype=dtype)
    pay_k, sc_k = quant_pages(pages, bits, interpret=True)
    pay_r, sc_r = ref.quant_kv_page(pages, bits)
    np.testing.assert_allclose(np.asarray(sc_k), np.asarray(sc_r), rtol=1e-6)
    deq_k = dequant_pages(pay_k, sc_k, bits, jnp.float32, interpret=True)
    deq_r = ref.dequant_kv_page(pay_r, sc_r, bits)
    # <= 1 quantization step anywhere; >98% identical payloads.
    step = np.asarray(sc_r).max() * (1.0 if bits == 8 else 1.0)
    np.testing.assert_allclose(np.asarray(deq_k), np.asarray(deq_r), atol=step + 1e-6)
    mismatch = (np.asarray(pay_k) != np.asarray(pay_r)).mean()
    assert mismatch < 0.02, mismatch


@pytest.mark.parametrize("shape", SWEEP)
@pytest.mark.parametrize("bits", [8, 4])
def test_quant_roundtrip_error_bound(shape, bits):
    rng = np.random.default_rng(0)
    pages = _pages(rng, *shape, dtype=jnp.float32)
    pay, sc = ref.quant_kv_page(pages, bits)
    deq = ref.dequant_kv_page(pay, sc, bits)
    rel = np.linalg.norm(np.asarray(deq - pages)) / np.linalg.norm(np.asarray(pages))
    assert rel < (0.012 if bits == 8 else 0.12), rel


@pytest.mark.parametrize("kv,heads", [(1, 4), (2, 8), (4, 4), (8, 16)])
@pytest.mark.parametrize("bits", [8, 4])
def test_paged_attention_vs_ref(kv, heads, bits):
    rng = np.random.default_rng(7)
    P, T, HD, B, MP = 6, 16, 64, 3, 4
    pages = _pages(rng, P, T, kv, HD)
    kp, ks = ref.quant_kv_page(pages, bits)
    vp, vs = ref.quant_kv_page(pages * 0.3, bits)
    q = jnp.asarray(rng.normal(0, 1, (B, heads, HD)), jnp.float32)
    table = jnp.asarray(rng.integers(0, P, (B, MP)), jnp.int32)
    n_pages = jnp.asarray([MP, 1, 0], jnp.int32)
    out_k = paged_quant_attention(q, kp, ks, vp, vs, table, n_pages, bits, interpret=True)
    out_r = ref.paged_quant_attention(q, kp, ks, vp, vs, table, n_pages, bits)
    for name, a, b in zip(["out", "m", "l", "mass", "base"], out_k, out_r):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4, err_msg=name
        )


def test_merge_partials_matches_monolithic_softmax():
    """Splitting a KV set into pools + merging partials == one softmax."""
    rng = np.random.default_rng(3)
    B, H, HD, S = 2, 4, 32, 64
    q = jnp.asarray(rng.normal(0, 1, (B, H, HD)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, S, H, HD)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, S, H, HD)), jnp.float32)
    full = ref.dense_recent_attention(q, k, v, S)
    out_full = full[0] / jnp.maximum(full[2], 1e-30)[..., None]
    p1 = ref.dense_recent_attention(q, k[:, :32], v[:, :32], 32)
    p2 = ref.dense_recent_attention(q, k[:, 32:], v[:, 32:], 32)
    merged = ref.merge_partials([p1, p2])
    np.testing.assert_allclose(np.asarray(merged), np.asarray(out_full), rtol=1e-5, atol=1e-5)


def test_tiered_decode_attention_quality():
    """Tiered (int8 warm + int4 cold) output stays close to exact bf16."""
    rng = np.random.default_rng(11)
    B, H, KV, HD, T = 2, 8, 4, 64, 16
    n_warm, n_cold, R = 4, 4, 8
    S = (n_warm + n_cold) * T + R

    k_full = jnp.asarray(rng.normal(0, 1, (B, S, KV, HD)), jnp.float32)
    v_full = jnp.asarray(rng.normal(0, 1, (B, S, KV, HD)), jnp.float32)
    q = jnp.asarray(rng.normal(0, 1, (B, H, HD)), jnp.float32)

    pools = {}
    for name, bits, lo, hi in (("warm", 8, 0, n_warm), ("cold", 4, n_warm, n_warm + n_cold)):
        kp_list, vp_list = [], []
        for b in range(B):
            for p in range(lo, hi):
                sl = slice(p * T, (p + 1) * T)
                kp_list.append(k_full[b, sl])
                vp_list.append(v_full[b, sl])
        kp, ks = ref.quant_kv_page(jnp.stack(kp_list), bits)
        vp, vs = ref.quant_kv_page(jnp.stack(vp_list), bits)
        n = hi - lo
        table = jnp.asarray([[b * n + i for i in range(n)] for b in range(B)], jnp.int32)
        pools[name] = dict(k_pages=kp, k_scales=ks, v_pages=vp, v_scales=vs,
                           page_table=table, n_pages=jnp.full((B,), n, jnp.int32), bits=bits)

    recent_k = k_full[:, -R:]
    recent_v = v_full[:, -R:]
    out_tiered = ops.tiered_decode_attention(q, pools, recent_k, recent_v, R)
    exact = ref.dense_recent_attention(q, k_full, v_full, S)
    out_exact = exact[0] / jnp.maximum(exact[2], 1e-30)[..., None]
    rel = float(jnp.linalg.norm(out_tiered - out_exact) / jnp.linalg.norm(out_exact))
    # int4 absmax on N(0,1) data has ~11% elementwise error (worst case for
    # the cold tier); real KV distributions are smoother (see fig3 bench).
    assert rel < 0.12, rel


def test_telemetry_hotness_sums_to_one():
    """Normalized page hotness + recent-window share == full softmax mass."""
    rng = np.random.default_rng(5)
    B, H, KV, HD, T, P, MP, R = 2, 4, 2, 32, 8, 6, 4, 4
    pages = _pages(rng, P, T, KV, HD)
    kp, ks = ref.quant_kv_page(pages, 8)
    vp, vs = ref.quant_kv_page(pages, 8)
    pools = {"warm": dict(k_pages=kp, k_scales=ks, v_pages=vp, v_scales=vs,
                          page_table=jnp.asarray(rng.integers(0, P, (B, MP)), jnp.int32),
                          n_pages=jnp.full((B,), MP, jnp.int32), bits=8)}
    recent_k = _pages(rng, 1, R, KV, HD)[0][None].repeat(B, 0).astype(jnp.float32)
    recent_v = recent_k
    q = jnp.asarray(rng.normal(0, 1, (B, H, HD)), jnp.float32)
    out, hot = ops.tiered_decode_attention(q, pools, recent_k, recent_v, R, with_telemetry=True)
    mass = np.asarray(hot["warm"]).sum(axis=1)
    assert (mass > 0).all() and (mass <= 1.0 + 1e-5).all()


def test_quant_property_randomized():
    for i, rng in cases(50):
        bits = draw_choice(rng, [8, 4])
        pages = _pages(rng, 2, 8, 2, 32, dtype=jnp.float32,
                       scale=draw_log_float(rng, 0.1, 10))
        pay, sc = ref.quant_kv_page(pages, bits)
        deq = ref.dequant_kv_page(pay, sc, bits)
        # Per-element error bounded by its group scale (one quantization step).
        err = np.abs(np.asarray(deq - pages))
        bound = np.asarray(sc)[..., None] * 0.51 + 1e-7
        assert (err <= bound).all(), (i, bits)


# ---------------------------------------------------------------------------
# fused transcode kernel (the batched migration path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", SWEEP)
@pytest.mark.parametrize("route", [(8, 4), (4, 8)])
def test_transcode_pages_vs_ref_composition(shape, route):
    """Fused transcode == dequant -> requant composition, interpret mode."""
    src_bits, dst_bits = route
    rng = np.random.default_rng(21)
    pages = _pages(rng, *shape, dtype=jnp.float32)
    pay, sc = ref.quant_kv_page(pages, src_bits)
    k_pay, k_sc = transcode_pages(pay, sc, src_bits, dst_bits, interpret=True)
    r_pay, r_sc = ref.quant_kv_page(ref.dequant_kv_page(pay, sc, src_bits), dst_bits)
    np.testing.assert_allclose(np.asarray(k_sc), np.asarray(r_sc), rtol=1e-6)
    # Payloads may differ only where a round-to-nearest tie flips: bound the
    # dequantized disagreement by one quantization step of the new scale.
    deq_k = ref.dequant_kv_page(k_pay, k_sc, dst_bits)
    deq_r = ref.dequant_kv_page(r_pay, r_sc, dst_bits)
    step = np.asarray(r_sc).max()
    np.testing.assert_allclose(np.asarray(deq_k), np.asarray(deq_r), atol=step + 1e-6)
    mismatch = (np.asarray(k_pay) != np.asarray(r_pay)).mean()
    assert mismatch < 0.02, mismatch


@pytest.mark.parametrize("route", [(8, 4), (4, 8)])
def test_transcode_pages_ops_dispatch(route):
    """ops.transcode_pages: pallas and ref backends agree; same-width is
    the identity (the same-codec fast path never transcodes)."""
    src_bits, dst_bits = route
    rng = np.random.default_rng(5)
    pages = _pages(rng, 3, 8, 2, 32, dtype=jnp.float32)
    pay, sc = ref.quant_kv_page(pages, src_bits)
    try:
        ops.use_pallas(False)
        rp, rs = ops.transcode_pages(pay, sc, src_bits, dst_bits)
    finally:
        ops.use_pallas(True)
    kp, ks = ops.transcode_pages(pay, sc, src_bits, dst_bits)
    np.testing.assert_array_equal(np.asarray(kp), np.asarray(rp))
    np.testing.assert_allclose(np.asarray(ks), np.asarray(rs), rtol=1e-6)
    ip, isc = ops.transcode_pages(pay, sc, src_bits, src_bits)
    assert ip is pay and isc is sc


def test_transcode_roundtrip_error_bounded():
    """int8 -> int4 -> int8 stays within int4 quantization error of the
    int8 dequant (migrating down and back must not compound losses)."""
    for i, rng in cases(50):
        pages = _pages(rng, 2, 8, 2, 32, dtype=jnp.float32,
                       scale=draw_log_float(rng, 0.1, 10))
        pay8, sc8 = ref.quant_kv_page(pages, 8)
        x8 = np.asarray(ref.dequant_kv_page(pay8, sc8, 8))
        pay4, sc4 = transcode_pages(pay8, sc8, 8, 4, interpret=True)
        pay8b, sc8b = transcode_pages(pay4, sc4, 4, 8, interpret=True)
        x8b = np.asarray(ref.dequant_kv_page(pay8b, sc8b, 8))
        bound = np.asarray(sc4)[..., None] * 0.51 + np.asarray(sc8b)[..., None] * 0.51 + 1e-6
        assert (np.abs(x8b - x8) <= bound).all(), i


def test_paged_attention_slot_pos_equivalence():
    """Explicit slot positions (SP shards pass these) == default iota."""
    rng = np.random.default_rng(9)
    P_, T, KV, HD, B, MP = 5, 8, 2, 32, 2, 4
    pages = _pages(rng, P_, T, KV, HD)
    kp, ks = ref.quant_kv_page(pages, 8)
    vp, vs = ref.quant_kv_page(pages, 8)
    q = jnp.asarray(rng.normal(0, 1, (B, 4, HD)), jnp.float32)
    table = jnp.asarray(rng.integers(0, P_, (B, MP)), jnp.int32)
    n = jnp.asarray([3, 2], jnp.int32)
    base = ref.paged_quant_attention(q, kp, ks, vp, vs, table, n, 8)
    pos = jnp.broadcast_to(jnp.arange(MP, dtype=jnp.int32)[None], (B, MP))
    with_pos = ref.paged_quant_attention(q, kp, ks, vp, vs, table, n, 8, slot_pos=pos)
    for a, b in zip(base, with_pos):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    # Shifted positions change validity (SP shard with offset slots).
    pos2 = pos + 2
    shifted = ref.paged_quant_attention(q, kp, ks, vp, vs, table, n, 8, slot_pos=pos2)
    assert float(shifted[2].sum()) < float(base[2].sum())  # fewer valid slots
