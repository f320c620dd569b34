"""Codec-class-major pool storage: shared class buffers, global-row
addressing, zero-concat fused operands, and same-class table-edit migration.

Covers the class-major contract end to end:

  * 3- and 4-pool deployments whose same-class pools alias ONE class buffer
    match the per-pool launch oracle on outputs and normalized hotness with
    ZERO per-step concat copy-bytes;
  * host-only and single-class launches (the other codec class is empty —
    its 1-row dummy buffer must be unaddressable);
  * one validated ``page_tokens`` per fused launch — mixed page sizes raise
    instead of silently mis-scaling sentinel mass;
  * ``SlotAllocator.free`` raises on unknown/double frees, and
    ``exchange_slots`` conserves capacity while enforcing dst quota;
  * same-class migration is a pure table edit (rows stay put, no transcode
    dispatch, no media bytes) on both the blocking executor and the async
    marker path, which stays bit-identical to the serial oracle;
  * a seeded property test that no sequence of migrations/releases ever
    aliases two live pages onto one global class row.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.manager import ManagerConfig
from repro.core.pools import ClassPartition, SlotAllocator, exchange_slots
from repro.kernels import ops, ref
from repro.serving.kv_cache import COLD, HOST4, HOST8, WARM, TieredKVCache

from proptest import cases, draw_int
from test_migration import CFG, check_table_invariants, fill_cache

B, H, KV, HD, T, R = 2, 8, 2, 32, 8, 6
TOL = dict(rtol=2e-4, atol=2e-4)


@pytest.fixture(autouse=True)
def _restore_ops_toggles():
    yield
    ops.use_pallas(True)
    ops.use_fused(True)


def _class_pools(bits_seq, rng, rows_per_pool=6, mp=4):
    """Class-major pools: one shared buffer per codec width, each pool
    owning a contiguous global-row range (the ``TieredKVCache`` layout)."""
    buf = {}
    for bits in sorted(set(bits_seq)):
        rows = rows_per_pool * bits_seq.count(bits)
        pages = jnp.asarray(rng.normal(0, 1, (rows, T, KV, HD)), jnp.bfloat16)
        kp, ks = ref.quant_kv_page(pages, bits)
        vp, vs = ref.quant_kv_page(pages * 0.5, bits)
        buf[bits] = dict(k_pages=kp, k_scales=ks, v_pages=vp, v_scales=vs)
    pools, base = {}, {b: 0 for b in buf}
    for i, bits in enumerate(bits_seq):
        table = jnp.asarray(
            base[bits] + rng.integers(0, rows_per_pool, (B, mp)), jnp.int32
        )
        base[bits] += rows_per_pool
        pools[f"t{i}"] = dict(
            **buf[bits], page_table=table,
            n_pages=jnp.asarray(rng.integers(1, mp + 1, B), jnp.int32),
            bits=bits,
        )
    return pools


def _mk_host(rng, hs=5, mp=3, page_tokens=T):
    return dict(
        summary=jnp.asarray(rng.normal(0, 1, (hs, KV, HD)), jnp.float32),
        table=jnp.asarray(rng.integers(0, hs, (B, mp)), jnp.int32),
        n=jnp.asarray([2, 3], jnp.int32), page_tokens=page_tokens,
    )


def _inputs(rng):
    q = jnp.asarray(rng.normal(0, 1, (B, H, HD)), jnp.float32)
    rk = jnp.asarray(rng.normal(0, 1, (B, R, KV, HD)), jnp.bfloat16)
    rv = jnp.asarray(rng.normal(0, 1, (B, R, KV, HD)), jnp.bfloat16)
    return q, rk, rv, jnp.asarray([R, R // 2], jnp.int32)


def _assert_same(res_a, res_b):
    out_a, hot_a = res_a
    out_b, hot_b = res_b
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b), **TOL)
    assert set(hot_a) == set(hot_b)
    for k in hot_a:
        np.testing.assert_allclose(
            np.asarray(hot_a[k]), np.asarray(hot_b[k]), err_msg=k, **TOL
        )


# ---------------------------------------------------------------------------
# fused launch over shared class buffers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "bits_seq",
    [(8, 8, 8), (8, 8, 4), (4, 4, 4), (8, 8, 4, 4), (8, 8, 8, 8)],
)
def test_same_class_pools_fused_matches_oracle_zero_copy(bits_seq):
    """3/4-pool deployments with shared class buffers: fused == per-pool
    oracle and operand assembly concatenates NOTHING."""
    rng = np.random.default_rng(13)
    pools = _class_pools(tuple(bits_seq), rng)
    host = _mk_host(rng)
    q, rk, rv, rlen = _inputs(rng)

    ops.use_fused(True)
    ops.reset_launch_count()
    ops.reset_copy_bytes()
    fused = ops.tiered_decode_attention(q, pools, rk, rv, rlen,
                                        with_telemetry=True, host=host)
    assert ops.launch_count() == 1
    assert ops.concat_copy_bytes() == 0, "class-major layout must not concat"

    ops.use_fused(False)
    oracle = ops.tiered_decode_attention(q, pools, rk, rv, rlen,
                                         with_telemetry=True, host=host)
    _assert_same(fused, oracle)


def test_single_class_and_host_only_launches():
    """One codec class populated (the other class's dummy buffer must stay
    unaddressed), and the host-only / recent-only degenerate launches."""
    rng = np.random.default_rng(17)
    q, rk, rv, rlen = _inputs(rng)
    host = _mk_host(rng)
    for pools, h in [
        (_class_pools((8, 8, 8), rng), host),  # int4 class empty
        (_class_pools((4, 4), rng), host),  # int8 class empty
        ({}, host),  # host-only
        ({}, None),  # recent-only
    ]:
        ops.use_fused(True)
        ops.reset_copy_bytes()
        fused = ops.tiered_decode_attention(q, pools, rk, rv, rlen,
                                            with_telemetry=True, host=h)
        assert ops.concat_copy_bytes() == 0
        ops.use_fused(False)
        oracle = ops.tiered_decode_attention(q, pools, rk, rv, rlen,
                                             with_telemetry=True, host=h)
        ops.use_fused(True)
        _assert_same(fused, oracle)


def test_stale_rows_cannot_address_empty_class_dummy():
    """A stale table entry past the valid prefix may carry any slot value —
    including one aliasing row 0 of the EMPTY int4 class's dummy buffer.
    ``TIER_INVALID`` masking (the single enforcement point) must keep it
    out of the launch: outputs match an oracle that never saw the row."""
    rng = np.random.default_rng(19)
    pools = _class_pools((8, 8), rng, mp=4)
    # Poison every out-of-prefix entry with row 0 (the dummy-aliasing slot)
    # and an in-range-looking value; n_pages masks them.
    for p in pools.values():
        tbl = np.asarray(p["page_table"]).copy()
        n = np.asarray(p["n_pages"])
        for b in range(B):
            tbl[b, n[b]:] = 0
        p["page_table"] = jnp.asarray(tbl)
    q, rk, rv, rlen = _inputs(rng)
    ops.use_fused(True)
    fused = ops.tiered_decode_attention(q, pools, rk, rv, rlen,
                                        with_telemetry=True)
    ops.use_fused(False)
    oracle = ops.tiered_decode_attention(q, pools, rk, rv, rlen,
                                         with_telemetry=True)
    _assert_same(fused, oracle)
    # Stale entries contribute exactly zero hotness.
    _, hot = fused
    for name, p in pools.items():
        n = np.asarray(p["n_pages"])
        h = np.asarray(hot[name])
        for b in range(B):
            assert (h[b, n[b]:] == 0.0).all()


def test_valid_row_out_of_class_bounds_raises():
    """A VALID table entry addressing past the class buffer is a real bug
    (stale slot with a live tier code) and the eager bounds guard names it."""
    rng = np.random.default_rng(23)
    pools = _class_pools((8, 8), rng)
    bad = np.asarray(pools["t0"]["page_table"]).copy()
    bad[0, 0] = 10_000  # far outside the shared int8 buffer
    pools["t0"]["page_table"] = jnp.asarray(bad)
    q, rk, rv, rlen = _inputs(rng)
    ops.use_fused(True)
    with pytest.raises(IndexError, match="class row"):
        ops.tiered_decode_attention(q, pools, rk, rv, rlen, with_telemetry=True)


def test_mixed_page_tokens_raises():
    """One validated page_tokens per fused launch — a mismatched pool or
    host sentinel page size raises instead of mis-scaling sentinel mass."""
    rng = np.random.default_rng(29)
    q, rk, rv, rlen = _inputs(rng)
    pools = _class_pools((8, 4), rng)
    # Pool with a different page shape.
    wrong = _class_pools((4,), np.random.default_rng(1), rows_per_pool=3)["t0"]
    wrong["k_pages"] = jnp.zeros((3, 2 * T, KV, HD // 2), jnp.uint8)
    for use_pallas in (True, False):
        ops.use_pallas(use_pallas)
        ops.use_fused(True)
        with pytest.raises(ValueError, match="mixed page_tokens"):
            ops.tiered_decode_attention(
                q, {**pools, "bad": wrong}, rk, rv, rlen, with_telemetry=True
            )
        # Host sentinels declaring a different page size.
        with pytest.raises(ValueError, match="mixed page_tokens"):
            ops.tiered_decode_attention(
                q, pools, rk, rv, rlen, with_telemetry=True,
                host=_mk_host(rng, page_tokens=2 * T),
            )
    ops.use_pallas(True)


# ---------------------------------------------------------------------------
# allocator hard contract
# ---------------------------------------------------------------------------


def test_slot_allocator_free_raises_on_unknown_and_double_free():
    a = SlotAllocator(4, base=10)
    s = a.alloc(block_id=1)
    assert 10 <= s < 14
    a.free(s)
    with pytest.raises(KeyError, match="unowned"):
        a.free(s)  # double free
    with pytest.raises(KeyError, match="unowned"):
        a.free(99)  # never allocated


def test_exchange_slots_conserves_capacity_and_enforces_quota():
    src = SlotAllocator(3, base=0)
    dst = SlotAllocator(3, tenant_quota={"a": 1}, base=3)
    s = src.alloc(block_id=7)
    with pytest.raises(ValueError):
        exchange_slots(src, dst, s, 7)  # quota'd dst needs a tenant
    got = exchange_slots(src, dst, s, 7, tenant="a")
    assert got == s  # the page's global row is unchanged
    assert dst._owner[s] == 7 and s not in src._owner
    # Free + owned conserved on both sides.
    assert len(src._free) + len(src._owner) == 3
    assert len(dst._free) + len(dst._owner) == 3
    assert dst.used_by("a") == 1
    s2 = src.alloc(block_id=8)
    with pytest.raises(MemoryError, match="quota"):
        exchange_slots(src, dst, s2, 8, tenant="a")
    with pytest.raises(KeyError, match="not owned"):
        exchange_slots(src, dst, 999, 9, tenant="a")


def test_class_partition_layout():
    part = ClassPartition([("warm", 8, 5), ("cold", 8, 7)])
    assert part.base("warm") == 0 and part.base("cold") == 5
    assert part.class_rows(8) == 12
    assert part.class_rows(4) == 1  # empty class still gets a dummy row
    mixed = ClassPartition([("warm", 8, 5), ("cold", 4, 7)])
    assert mixed.base("cold") == 0  # separate class, separate row space
    with pytest.raises(ValueError):
        ClassPartition([("warm", 8, 5), ("warm", 8, 5)])


# ---------------------------------------------------------------------------
# same-class migration = table edits
# ---------------------------------------------------------------------------


def make88(async_migration=False, prefetch=False, warm_frac=0.5):
    return TieredKVCache(
        CFG, 2, 2, 8, 64, recent_window=16,
        manager_cfg=ManagerConfig(policy="analytical", alpha=0.5),
        warm_frac=warm_frac, async_migration=async_migration,
        prefetch=prefetch, pool_bits={"warm": 8, "cold": 8},
    )


def _class_rows_unique(cache):
    """No two live device pages may share a global class-buffer row."""
    for bits in (8, 4):
        rows = []
        for pool, level in (("warm", WARM), ("cold", COLD)):
            if cache._pool_bits[pool] != bits:
                continue
            live = np.where((cache.physical == level) & cache._page_exists)[0]
            rows.extend(int(cache._pool_slot[r]) for r in live)
        assert len(rows) == len(set(rows)), f"aliased class-{bits} rows"
    # Allocator books stay conserved and disjoint.
    wa, ca = cache._alloc["warm"], cache._alloc["cold"]
    assert len(wa._free) + len(wa._owner) == wa.capacity
    assert len(ca._free) + len(ca._owner) == ca.capacity
    if cache._pool_bits["warm"] == cache._pool_bits["cold"]:
        both = set(wa._free) | set(wa._owner) | set(ca._free) | set(ca._owner)
        assert len(both) == wa.capacity + ca.capacity


def test_same_class_blocking_move_is_pure_table_edit():
    c = make88()
    coords = fill_cache(c, np.random.default_rng(0), 24)
    rids = np.array([c.rid(*x) for x in coords[:8]], np.int64)
    ps = c._pool_slot[rids].copy()
    payload = np.asarray(c.state.c8_k)[ps].copy()
    kd = c.kernel_dispatches
    c.migrate_batch(rids, np.full(rids.size, COLD, np.int64))
    check_table_invariants(c)
    _class_rows_unique(c)
    assert (c.physical[rids] == COLD).all()
    np.testing.assert_array_equal(c._pool_slot[rids], ps)  # rows stayed put
    assert c.kernel_dispatches == kd  # no transcode dispatch
    np.testing.assert_array_equal(np.asarray(c.state.c8_k)[ps], payload)
    # ...and back up, still by table edit.
    c.migrate_batch(rids, np.full(rids.size, WARM, np.int64))
    check_table_invariants(c)
    np.testing.assert_array_equal(c._pool_slot[rids], ps)
    assert c.kernel_dispatches == kd


def test_async_same_class_matches_serial_and_moves_zero_bytes():
    """The marker path through stage/transcode/commit: bit-identical to the
    serial oracle, zero media bytes for the table-edit cohorts."""
    from test_migration import assert_same_state

    ca, cb = make88(async_migration=True), make88(async_migration=False)
    for c in (ca, cb):
        fill_cache(c, np.random.default_rng(3), 24)
    live = np.where(ca._page_exists)[0]
    # Same-class device cohort first: pure table edits, ZERO media bytes.
    dev_rids = live[:6]
    bytes0 = dict(ca.pipeline.media_bytes())
    for c in (ca, cb):
        c.pipeline.submit(
            c.plan_cohorts(dev_rids.copy(), np.full(6, COLD, np.int64))
        )
        if c.pipeline.busy:
            c.pipeline.drain()
    assert_same_state(ca, cb)
    _class_rows_unique(ca)
    delta = {k: v - bytes0[k] for k, v in ca.pipeline.media_bytes().items()}
    assert all(v == 0 for v in delta.values()), delta
    # Host swap-out is a real spill and pays for its bytes.
    host_rids = live[6:10]
    for c in (ca, cb):
        c.pipeline.submit(
            c.plan_cohorts(host_rids.copy(), np.full(4, HOST4, np.int64))
        )
        if c.pipeline.busy:
            c.pipeline.drain()
    assert_same_state(ca, cb)
    delta = {k: v - bytes0[k] for k, v in ca.pipeline.media_bytes().items()}
    assert delta["host_dram_pcie"] > 0
    # Promotions back (host -> device crosses codecs and pays; the
    # same-class leg still edits tables only).
    for c in (ca, cb):
        c.pipeline.submit(
            c.plan_cohorts(live[:10].copy(), np.full(10, WARM, np.int64))
        )
        if c.pipeline.busy:
            c.pipeline.drain()
    assert_same_state(ca, cb)
    _class_rows_unique(ca)


def test_release_and_prefetch_claim_under_class_addressing():
    """Prefetch claim -> promotion commit scatters into the class buffer;
    release under class addressing frees global rows exactly once."""
    c = make88(async_migration=True, prefetch=True, warm_frac=1.0)
    fill_cache(c, np.random.default_rng(5), 24)
    live = np.where(c._page_exists)[0]
    host = live[12:]
    c.migrate_batch(host, np.full(host.size, HOST4, np.int64))
    _class_rows_unique(c)
    # Warm the predictor toward the host pages, tick the speculative path.
    base = np.zeros(c.n_regions)
    base[live[:12]] = 5.0
    c.manager.record_access_counts(base)
    c.manager.close_telemetry()
    rising = np.zeros(c.n_regions)
    rising[host] = 50.0
    c.manager.record_host_mass(rising)
    for _ in range(8):
        c.prefetch_tick()
    assert c.pipeline.prefetch_staged > 0
    # Boundary promotes the held pages: claims commit into the c8 buffer.
    c.manager.placement[host] = HOST4
    cohorts = c.plan_cohorts(host, np.full(host.size, WARM, np.int64))
    prestaged = {}
    for crids, s, _d in cohorts:
        prestaged.update(c.pipeline.claim_prefetched(crids, s))
    assert c.pipeline.prefetch_hits > 0
    c.pipeline.discard_speculative()
    c.pipeline.submit(cohorts, prestaged=prestaged or None)
    if c.pipeline.busy:
        c.pipeline.drain()
    check_table_invariants(c)
    _class_rows_unique(c)
    assert (c.physical[host] == WARM).all()
    # Release both batch slots: every global row returns exactly once.
    c.release_slot_pages(0)
    c.release_slot_pages(1)
    _class_rows_unique(c)
    assert not c._page_exists.any()
    assert len(c._free_warm) == c._alloc["warm"].capacity
    assert len(c._free_cold) == c._alloc["cold"].capacity


def test_table_edits_never_alias_class_rows_property():
    """Seeded property test: random migration/release sequences on a
    same-class deployment never alias two live pages onto one class row."""
    for i, rng in cases(8):
        async_mode = bool(i % 2)
        c = make88(async_migration=async_mode)
        n_pages = draw_int(rng, 8, 24)
        fill_cache(c, rng, n_pages)
        _class_rows_unique(c)
        for _ in range(draw_int(rng, 3, 6)):
            live = np.where(c._page_exists)[0]
            if live.size == 0:
                break
            k = draw_int(rng, 1, max(live.size // 2, 1))
            rids = rng.choice(live, size=k, replace=False).astype(np.int64)
            dsts = rng.choice(
                [WARM, COLD, HOST8, HOST4], size=k, replace=True
            ).astype(np.int64)
            if async_mode:
                c.pipeline.submit(c.plan_cohorts(rids, dsts))
                if c.pipeline.busy:
                    c.pipeline.drain()
            else:
                c.migrate_batch(rids, dsts)
            check_table_invariants(c)
            _class_rows_unique(c)
        if draw_int(rng, 0, 1):
            c.release_slot_pages(draw_int(rng, 0, c.bs - 1))
            _class_rows_unique(c)


def test_default_split_unchanged_by_class_major_layout():
    """The (8, 4) default: both allocators base at 0, class buffers have
    the per-pool shapes, and the engine's tier ids are the classic ones."""
    from test_migration import make_cache

    c = make_cache()
    assert c._alloc["warm"].base == 0 and c._alloc["cold"].base == 0
    assert c._cls == {"warm": "c8", "cold": "c4"}
    assert c.state.c8_k.shape[0] == c._alloc["warm"].capacity
    assert c.state.c4_k.shape[0] == c._alloc["cold"].capacity
    ids = [t.tid for t in c.manager.tierset.tiers]
    assert ids == ["C5", "C9", "C7", "C10"]
    c88 = make88()
    ids88 = [t.tid for t in c88.manager.tierset.tiers]
    assert ids88 == ["C5", "C6", "C7", "C10"]
    # Same-class pools stack into one class buffer.
    assert (
        c88.state.c8_k.shape[0]
        == c88._alloc["warm"].capacity + c88._alloc["cold"].capacity
    )
    assert c88.state.c4_k.shape[0] == 1  # empty class: dummy row only
    assert c88._alloc["cold"].base == c88._alloc["warm"].capacity
