"""Single-launch fused tiered attention vs the per-pool oracle.

Covers the megakernel contract: fused == per-pool == pure-jnp ref for both
outputs and normalized page hotness (fp32 tolerance) across mixed int8/int4
pools; exactly one Pallas launch per decode step independent of tier count;
empty-pool and all-host-pages edge cases; host sentinel would-have-touched
mass matching the ref oracle; the compacted page walk (holes, unequal rows,
the walk bound, several pages per grid step) against the ref in output and
raw per-slot mass/base; the in-engine host-mass route into the prefetch
predictor; and placement neutrality of the host telemetry.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ops, paged_attention, ref

B, H, KV, HD, T, R = 2, 8, 2, 32, 8, 6
TOL = dict(rtol=2e-4, atol=2e-4)


@pytest.fixture(autouse=True)
def _restore_ops_toggles():
    yield
    ops.use_pallas(True)
    ops.use_fused(True)


def _mk_pool(rng, n_pages, bits, mp, n_valid):
    pages = jnp.asarray(rng.normal(0, 1, (n_pages, T, KV, HD)), jnp.bfloat16)
    kp, ks = ref.quant_kv_page(pages, bits)
    vp, vs = ref.quant_kv_page(pages * 0.5, bits)
    return dict(
        k_pages=kp, k_scales=ks, v_pages=vp, v_scales=vs,
        page_table=jnp.asarray(rng.integers(0, n_pages, (B, mp)), jnp.int32),
        n_pages=jnp.asarray(n_valid, jnp.int32), bits=bits,
    )


def _mk_host(rng, hs=5, mp=3, n=(2, 3), page_tokens=T):
    return dict(
        summary=jnp.asarray(rng.normal(0, 1, (hs, KV, HD)), jnp.float32),
        table=jnp.asarray(rng.integers(0, hs, (B, mp)), jnp.int32),
        n=jnp.asarray(n, jnp.int32), page_tokens=page_tokens,
    )


def _inputs(rng):
    q = jnp.asarray(rng.normal(0, 1, (B, H, HD)), jnp.float32)
    rk = jnp.asarray(rng.normal(0, 1, (B, R, KV, HD)), jnp.bfloat16)
    rv = jnp.asarray(rng.normal(0, 1, (B, R, KV, HD)), jnp.bfloat16)
    rlen = jnp.asarray([R, R // 2], jnp.int32)
    return q, rk, rv, rlen


def _assert_same(res_a, res_b):
    out_a, hot_a = res_a
    out_b, hot_b = res_b
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b), **TOL)
    assert set(hot_a) == set(hot_b)
    for k in hot_a:
        np.testing.assert_allclose(
            np.asarray(hot_a[k]), np.asarray(hot_b[k]), err_msg=k, **TOL
        )


@pytest.mark.parametrize("n_tiers", [2, 3, 4])
def test_fused_equals_per_pool_mixed_codecs(n_tiers):
    rng = np.random.default_rng(7)
    bits_seq = (8, 4, 8, 4)
    pools = {
        f"t{i}": _mk_pool(rng, 6, bits_seq[i], 4, rng.integers(1, 5, B))
        for i in range(n_tiers)
    }
    host = _mk_host(rng)
    q, rk, rv, rlen = _inputs(rng)

    ops.use_fused(True)
    fused = ops.tiered_decode_attention(q, pools, rk, rv, rlen,
                                        with_telemetry=True, host=host)
    ops.use_fused(False)
    oracle = ops.tiered_decode_attention(q, pools, rk, rv, rlen,
                                         with_telemetry=True, host=host)
    _assert_same(fused, oracle)


def test_fused_kernel_matches_jnp_ref():
    rng = np.random.default_rng(11)
    pools = {"warm": _mk_pool(rng, 5, 8, 4, [4, 2]),
             "cold": _mk_pool(rng, 5, 4, 4, [3, 1])}
    host = _mk_host(rng)
    q, rk, rv, rlen = _inputs(rng)
    ops.use_fused(True)
    fused = ops.tiered_decode_attention(q, pools, rk, rv, rlen,
                                        with_telemetry=True, host=host)
    ops.use_pallas(False)
    jref = ops.tiered_decode_attention(q, pools, rk, rv, rlen,
                                       with_telemetry=True, host=host)
    _assert_same(fused, jref)


def test_single_launch_independent_of_tier_count():
    rng = np.random.default_rng(0)
    q, rk, rv, rlen = _inputs(rng)
    for n in (1, 2, 4):
        pools = {f"t{i}": _mk_pool(rng, 4, (8, 4)[i % 2], 3, [3, 2])
                 for i in range(n)}
        ops.use_fused(True)
        ops.reset_launch_count()
        ops.tiered_decode_attention(q, pools, rk, rv, rlen,
                                    with_telemetry=True, host=_mk_host(rng))
        assert ops.launch_count() == 1, f"{n} tiers"
        ops.use_fused(False)
        ops.reset_launch_count()
        ops.tiered_decode_attention(q, pools, rk, rv, rlen)
        assert ops.launch_count() == n
        ops.use_fused(True)
    assert ops.decode_launches_per_step(n_pools=4) == 1
    ops.use_fused(False)
    assert ops.decode_launches_per_step(n_pools=4) == 4


def test_empty_pool_and_all_host_edges():
    rng = np.random.default_rng(3)
    q, rk, rv, rlen = _inputs(rng)
    host = _mk_host(rng)
    # Empty pool: a pool present but with zero valid pages everywhere.
    empty = _mk_pool(rng, 2, 8, 3, [0, 0])
    cases = [
        ({"warm": empty}, host),  # empty pool + host sentinels
        ({}, host),  # all pages host-resident: recent window only
        ({}, None),  # degenerate: recent window alone
    ]
    for pools, h in cases:
        ops.use_fused(True)
        fused = ops.tiered_decode_attention(q, pools, rk, rv, rlen,
                                            with_telemetry=True, host=h)
        ops.use_fused(False)
        oracle = ops.tiered_decode_attention(q, pools, rk, rv, rlen,
                                             with_telemetry=True, host=h)
        ops.use_fused(True)
        _assert_same(fused, oracle)
    # The empty pool contributes exactly zero hotness.
    out, hot = ops.tiered_decode_attention(
        q, {"warm": empty}, rk, rv, rlen, with_telemetry=True, host=host
    )
    assert float(np.abs(np.asarray(hot["warm"])).sum()) == 0.0
    assert float(np.asarray(hot["host"]).sum()) > 0.0


def _walk_case(name, rng):
    """(pools, host, walk) for one compacted-walk case. Unified rows are
    warm | cold | host columns; a pool's valid entries are a prefix of its
    table, so partly filled pools leave holes inside the unified row."""
    if name == "holes_unequal_rows":
        pools = {"warm": _mk_pool(rng, 9, 8, 6, [5, 1]),
                 "cold": _mk_pool(rng, 9, 4, 6, [2, 6])}
        return pools, _mk_host(rng, mp=4, n=(3, 0)), None
    if name == "mixed_int8_int4_rows":
        pools = {f"t{i}": _mk_pool(rng, 7, (8, 4)[i % 2], 4, [(3, 1, 4, 2)[i], (0, 4, 2, 3)[i]])
                 for i in range(4)}
        return pools, _mk_host(rng, mp=3, n=(2, 1)), None
    if name == "all_host_and_empty_rows":
        pools = {"warm": _mk_pool(rng, 4, 8, 3, [0, 0]),
                 "cold": _mk_pool(rng, 4, 4, 3, [0, 0])}
        return pools, _mk_host(rng, mp=5, n=(5, 0)), None
    if name == "row_at_walk_bound":
        pools = {"warm": _mk_pool(rng, 8, 8, 6, [6, 2]),
                 "cold": _mk_pool(rng, 8, 4, 6, [3, 1])}
        return pools, _mk_host(rng, mp=3, n=(2, 0)), 11
    assert name == "walk_bound_below_table_width"
    # As served: the bound is one sequence's pages, a third of the width.
    pools = {"warm": _mk_pool(rng, 8, 8, 6, [6, 3]),
             "cold": _mk_pool(rng, 8, 4, 6, [4, 5])}
    return pools, _mk_host(rng, mp=6, n=(0, 2)), 12


WALK_CASES = ["holes_unequal_rows", "mixed_int8_int4_rows", "all_host_and_empty_rows",
              "row_at_walk_bound", "walk_bound_below_table_width"]


@pytest.mark.parametrize("case", WALK_CASES)
def test_compacted_walk_matches_ref(case):
    """The kernel walks only held pages: output, merged logsumexp and the raw
    per-slot mass/base at the unified layout match the jnp oracle, with
    holes, unequal rows, all-host and empty rows, a row exactly at the
    walk bound and a bound below the table width."""
    rng = np.random.default_rng(WALK_CASES.index(case) + 40)
    pools, host, walk = _walk_case(case, rng)
    q, rk, rv, rlen = _inputs(rng)
    (k8, s8k, v8, s8v, k4, s4k, v4, s4v, summary,
     uni_slot, uni_tier, t, layout) = ops._unified_operands(q, pools, rk, host)
    held = (np.asarray(uni_tier) >= 0).sum(axis=1)
    if case == "row_at_walk_bound":
        assert held.max() == walk
    out, m, l, mass, base = paged_attention.fused_tiered_attention(
        q, k8, s8k, v8, s8v, k4, s4k, v4, s4v, summary, rk, rv, uni_slot, uni_tier,
        rlen, page_tokens=t, walk=walk, interpret=True)
    rout, rm, rl, masses = ref.fused_tiered_attention(q, pools, rk, rv, rlen, host=host)
    np.testing.assert_allclose(np.asarray(out), np.asarray(rout), **TOL)
    # The merged logsumexp; (m, l) alone differ by convention where a pool
    # is empty (the oracle merges its m=0 partial).
    np.testing.assert_allclose(np.asarray(m + jnp.log(l)), np.asarray(rm + jnp.log(rl)), **TOL)
    assert set(layout) == set(masses)
    for name, (lo, hi) in layout.items():
        rmass, rbase = masses[name]
        np.testing.assert_allclose(np.asarray(mass[:, lo:hi]), np.asarray(rmass),
                                   err_msg=name, **TOL)
        np.testing.assert_allclose(np.asarray(base[:, lo:hi]), np.asarray(rbase),
                                   err_msg=name, **TOL)
    # Slots that hold no page read exactly 0 mass at a NEG_INF base.
    empty = np.asarray(uni_tier) < 0
    assert (np.asarray(mass)[empty] == 0.0).all()
    assert (np.asarray(base)[empty] == paged_attention.NEG_INF).all()


def test_walk_bound_check_raises():
    """An eager call whose rows hold more valid slots than the walk bound
    raises instead of silently dropping pages."""
    rng = np.random.default_rng(9)
    pools = {"warm": _mk_pool(rng, 6, 8, 4, [4, 1]),
             "cold": _mk_pool(rng, 6, 4, 4, [2, 1])}
    host = _mk_host(rng, mp=3, n=(1, 0))
    q, rk, rv, rlen = _inputs(rng)
    ops.tiered_decode_attention(q, pools, rk, rv, rlen, with_telemetry=True,
                                host=host, max_pages=7)
    with pytest.raises(ValueError, match="walk bound is 6"):
        ops.tiered_decode_attention(q, pools, rk, rv, rlen, with_telemetry=True,
                                    host=host, max_pages=6)


def test_compact_walk_and_scatter_round_trip():
    """``compact_walk`` lists held columns in column order with their slot
    and tier; ``scatter_walk`` puts walk-ordered values back at those
    columns and 0 / NEG_INF elsewhere."""
    tier = np.array([[0, -1, 1, -1, 2, 0], [-1, -1, -1, -1, -1, -1], [2, 2, -1, 1, -1, -1]],
                    np.int32)
    slot = np.arange(18, dtype=np.int32).reshape(3, 6) + 100
    wslot, wtier, nwalk, pick = paged_attention.compact_walk(jnp.asarray(slot),
                                                             jnp.asarray(tier), 4)
    np.testing.assert_array_equal(np.asarray(nwalk), [4, 0, 3])
    np.testing.assert_array_equal(np.asarray(wtier),
                                  [[0, 1, 2, 0], [-1, -1, -1, -1], [2, 2, 1, -1]])
    np.testing.assert_array_equal(np.asarray(wslot)[0], [100, 102, 104, 105])
    np.testing.assert_array_equal(np.asarray(wslot)[2, :3], [112, 113, 115])
    vals = jnp.asarray(np.arange(12, dtype=np.float32).reshape(3, 4) + 1)
    mass, base = paged_attention.scatter_walk(pick, vals, -vals)
    expect = np.zeros((3, 6), np.float32)
    expect[0, [0, 2, 4, 5]] = [1, 2, 3, 4]
    expect[2, [0, 1, 3]] = [9, 10, 11]
    np.testing.assert_array_equal(np.asarray(mass), expect)
    np.testing.assert_array_equal(np.asarray(base),
                                  np.where(expect > 0, -expect, paged_attention.NEG_INF))


def test_host_mass_matches_ref_oracle():
    """The kernel's sentinel rows emit exactly ref.host_page_mass, rebased
    by the same merged (m, l) normalization as real page masses."""
    rng = np.random.default_rng(5)
    pools = {"warm": _mk_pool(rng, 4, 8, 3, [3, 2])}
    # One validated page_tokens per launch: the host sentinels must declare
    # the pools' page size (a mismatch raises — see test_class_major.py).
    host = _mk_host(rng, page_tokens=T)
    q, rk, rv, rlen = _inputs(rng)
    ops.use_fused(True)
    _, hot = ops.tiered_decode_attention(q, pools, rk, rv, rlen,
                                         with_telemetry=True, host=host)
    # Rebuild the normalization from the jnp oracle's merged stats.
    out, m_tot, l_tot, masses = ref.fused_tiered_attention(
        q, pools, rk, rv, rlen, host=host
    )
    mass, base = ref.host_page_mass(
        q, host["summary"], host["table"], host["n"], host["page_tokens"]
    )
    np.testing.assert_allclose(np.asarray(masses["host"][0]), np.asarray(mass))
    expect = ops.page_hotness(mass, base, m_tot, l_tot)
    np.testing.assert_allclose(
        np.asarray(hot["host"]), np.asarray(expect), **TOL
    )
    # Invalid sentinel rows carry zero mass.
    nvalid = np.asarray(host["n"])
    hostm = np.asarray(hot["host"])
    for b in range(B):
        assert (hostm[b, nvalid[b]:] == 0.0).all()


def test_host_mass_flows_to_predictor_not_placement():
    """Engine route: the cache folds sentinel telemetry into
    manager.record_host_mass (prefetch candidates) while the placement-
    driving access counts — and therefore plans — are untouched."""
    from repro.configs.base import ModelConfig
    from repro.core.manager import ManagerConfig
    from repro.serving.kv_cache import HOST4, TieredKVCache

    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                      head_dim=16)

    def build():
        c = TieredKVCache(cfg, 1, 2, 8, 64, recent_window=16,
                          manager_cfg=ManagerConfig(policy="analytical",
                                                    alpha=0.5, window_steps=4),
                          warm_frac=1.0)
        rng = np.random.default_rng(0)
        coords = [(0, sl, pg) for sl in range(2) for pg in range(c.max_pages)]
        k = rng.normal(0, 1, (len(coords), 8, cfg.n_kv_heads, 16)).astype(np.float32)
        c.append_pages(coords, jnp.asarray(k), jnp.asarray(k * 0.3))
        # Push half the pages to the int4 host tier -> sentinels appear.
        host_rids = np.arange(c.n_regions)[::2]
        c.migrate_batch(host_rids, np.full(host_rids.size, HOST4, np.int64))
        return c, host_rids

    a, host_rids = build()
    st = a.state
    assert int(np.asarray(st.host_n).sum()) == host_rids.size
    telemetry = {
        "warm": np.full((a.la, a.bs, a.max_pages), 0.01),
        "cold": np.zeros((a.la, a.bs, a.max_pages)),
        "host": np.full((a.la, a.bs, a.max_pages), 0.05),
    }
    a.record_telemetry(telemetry)
    # Host mass reached the predictor accumulator for exactly the host rids...
    assert (a.manager.host_mass[host_rids] > 0).all()
    non_host = np.setdiff1d(np.arange(a.n_regions), host_rids)
    assert (a.manager.host_mass[non_host] == 0).all()
    assert a.quality_skipped_mass > 0
    # ...and the placement-driving counts saw none of it: plans match a
    # cache that never received the host key (oracle-identical placements).
    b, _ = build()
    b.record_telemetry({k: telemetry[k] for k in ("warm", "cold")})
    np.testing.assert_array_equal(
        a.manager.telemetry._accum, b.manager.telemetry._accum
    )
    plan_a, _ = a.end_window()
    plan_b, _ = b.end_window()
    np.testing.assert_array_equal(plan_a.regions, plan_b.regions)
    np.testing.assert_array_equal(plan_a.dst, plan_b.dst)
    np.testing.assert_array_equal(a.physical, b.physical)
    # Window close resets the within-window host-mass accumulator.
    assert (a.manager.host_mass == 0).all()


def test_host_mass_qualifies_prefetch_candidates():
    """A host page with in-engine would-have-touched mass becomes a
    prefetch candidate even when the PEBS-analogue trend never saw it."""
    from repro.core.manager import ManagerConfig, TierScapeManager
    from repro.core.tiers import default_tierset

    ts = default_tierset()
    n = 16
    mgr = TierScapeManager(ts, n, region_bytes=ts.block_bytes,
                           cfg=ManagerConfig(policy="analytical"))
    mgr.record_access_counts(np.zeros(n))
    mgr.close_telemetry()  # predictor needs one closed window
    eligible = np.zeros(n, bool)
    eligible[3] = True
    # No trend, no host mass -> no candidates (seed behavior preserved).
    assert mgr.prefetch_candidates(eligible, top_k=4, max_regions=4).size == 0
    host_mass = np.zeros(n)
    host_mass[3] = 50.0
    mgr.record_host_mass(host_mass)
    cand = mgr.prefetch_candidates(eligible, top_k=4, max_regions=4)
    assert 3 in cand
    mgr.close_telemetry()
    assert mgr.prefetch_candidates(eligible, top_k=4, max_regions=4).size == 0


def test_sentinel_tables_track_every_host_transition():
    """host_table/host_n/host_summary slots stay consistent through batch
    migration, per-page migration, async stage/commit and release."""
    from repro.configs.base import ModelConfig
    from repro.core.manager import ManagerConfig
    from repro.serving.kv_cache import COLD, HOST4, HOST8, TieredKVCache

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                      head_dim=16)
    c = TieredKVCache(cfg, 2, 2, 8, 32, recent_window=16,
                      manager_cfg=ManagerConfig(policy="analytical",
                                                alpha=0.5, window_steps=4),
                      warm_frac=1.0)
    rng = np.random.default_rng(1)
    coords = [(la, sl, pg) for la in range(2) for sl in range(2)
              for pg in range(c.max_pages)]
    k = rng.normal(0, 1, (len(coords), 8, cfg.n_kv_heads, 16)).astype(np.float32)
    c.append_pages(coords, jnp.asarray(k), jnp.asarray(k * 0.3))

    def n_sentinels():
        return int(np.asarray(c.state.host_n).sum())

    def host_pages_live():
        return int((((c.physical == HOST4) | (c.physical == HOST8))
                    & c._page_exists).sum())

    assert n_sentinels() == host_pages_live() == 0
    rids = np.arange(c.n_regions)
    c.migrate_batch(rids[:6], np.full(6, HOST4, np.int64))
    assert n_sentinels() == host_pages_live() == 6
    assert (c._host_slot[rids[:6]] >= 0).all()
    # Host -> host retranscode keeps exactly one sentinel per page.
    c.migrate_batch(rids[:3], np.full(3, HOST8, np.int64))
    assert n_sentinels() == host_pages_live() == 6
    # Promotion back to a device pool retires the sentinel.
    c.migrate_batch(rids[:2], np.full(2, COLD, np.int64))
    assert n_sentinels() == host_pages_live() == 4
    assert (c._host_slot[rids[:2]] == -1).all()
    # Per-page oracle path.
    c.migrate(int(rids[2]), COLD)
    assert n_sentinels() == host_pages_live() == 3
    # Release frees a slot's sentinels with its pages.
    c.release_slot_pages(0)
    assert n_sentinels() == host_pages_live()
    assert (np.asarray(c.state.host_n)[:, 0] == 0).all()
    # Summary content: mean over T of the dequantized stored K payload.
    live = np.where(((c.physical == HOST4) | (c.physical == HOST8))
                    & c._page_exists)[0]
    r = int(live[0])
    layer, slot, _ = c.rid_coords(r)
    kp, ks, _, _ = c.host_pages[r]
    bits = 8 if int(c.physical[r]) == HOST8 else 4
    expect = np.asarray(ref.dequant_kv_page(jnp.asarray(kp), jnp.asarray(ks),
                                            bits)).mean(axis=0)
    got = np.asarray(c.state.host_summary[layer, int(c._host_slot[r])])
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)


def test_engine_fused_telemetry_live_and_launches_counted():
    """End-to-end: the engine's decode step now produces live warm/cold
    hotness plus host sentinel mass, and the dispatch proxy bills exactly
    n_layers launches per step (fused), not O(tiers)."""
    import jax

    from repro.configs.base import ModelConfig, TierScapeRunConfig
    from repro.models import Model
    from repro.serving import TieredEngine

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                      head_dim=16)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = TieredEngine(model, params, batch_slots=2, page_tokens=8,
                       max_seq_len=96, recent_window=16,
                       ts=TierScapeRunConfig(enabled=True, policy="analytical",
                                             alpha=0.3, window_steps=6))
    rng = np.random.default_rng(0)
    for _ in range(2):
        eng.submit(rng.integers(1, cfg.vocab_size, 48), max_new_tokens=12)
    stats = eng.run(max_steps=64)
    assert stats.completed == 2
    # Live device-pool telemetry reached the manager (pre-PR the engine's
    # jnp path emitted all-zero hotness).
    assert float(eng.cache.manager.telemetry.history.sum()) > 0.0
    assert stats.attn_launches == eng.la * stats.steps
    assert eng.cache.decode_steps_recorded == stats.steps

def test_walk_slots_proxy_follows_the_mode():
    """The fused path bills each row's held pages, one grid step each; the
    per-pool oracle every column of every pool's table."""
    held = np.array([0, 1, 5, 7])
    assert ops.decode_walk_slots(held, 8, 2) == 13
    ops.use_fused(False)
    assert ops.decode_walk_slots(held, 8, 2) == 2 * 8 * 4


def test_engine_walk_counters_and_page_bound():
    """The engine bills walked page slots and attended pages from host-side
    page counts, equal to what the device tables hold at each step; no
    slot ever holds more pages (warm + cold + host) than one sequence's
    max_pages, the walk bound the decode step passes."""
    import jax

    from repro.configs.base import ModelConfig, TierScapeRunConfig
    from repro.models import Model
    from repro.serving import TieredEngine

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                      head_dim=16)
    model = Model(cfg)
    eng = TieredEngine(model, model.init(jax.random.PRNGKey(1)), batch_slots=2,
                       page_tokens=8, max_seq_len=96, recent_window=16,
                       ts=TierScapeRunConfig(enabled=True, policy="analytical",
                                             alpha=0.0, window_steps=4))
    rng = np.random.default_rng(2)
    for n in (40, 56):
        eng.submit(rng.integers(1, cfg.vocab_size, n), max_new_tokens=24)
    walked = pages = held_max = 0
    while (any(s is not None for s in eng.slots) or eng.queue) and eng.stats.steps < 64:
        eng._fill_slots()
        st = eng.cache.state
        held = np.asarray(st.warm_n) + np.asarray(st.cold_n) + np.asarray(st.host_n)
        held_max = max(held_max, int(held.max()))
        assert held_max <= eng.cache.max_pages
        walked += ops.decode_walk_slots(held.ravel(), eng.cache.max_pages, 2)
        pages += int(held.sum())
        eng.step()
    stats = eng.finish()
    assert stats.completed == 2 and held_max > 0
    assert (stats.attn_walk_slots, stats.attn_pages) == (walked, pages)
    assert 0 < stats.attn_pages <= stats.attn_walk_slots
