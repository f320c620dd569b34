"""Batched migration executor equivalence + plan-pricing parity.

The batched cohort executor (``TieredKVCache.migrate_batch``) must be an
exact drop-in for the per-page loop (``migrate`` per region, the seed
semantics): same physical placements, same logical pool contents keyed by
region, same page-table membership, same host-tier dicts — and the
vectorized ``TierScapeManager._plan`` must price exactly like the per-page
reference loop, including the same-codec fast path.

Payloads are compared bit-exactly. Scales are compared at float tolerance:
on the same-codec fast path the batched executor copies scales verbatim
while the per-page loop requantizes (an identity on payloads, but 1-2 ulp
of float noise on scales).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.manager import ManagerConfig, make_manager
from repro.serving.kv_cache import COLD, HOST4, HOST8, WARM, TieredKVCache

from proptest import cases, draw_int

CFG = ModelConfig(
    name="tiny", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab_size=128, head_dim=16,
)


def make_cache(layers=2, slots=2, page_tokens=8, max_seq=64, warm_frac=0.5):
    return TieredKVCache(
        CFG, layers, slots, page_tokens, max_seq, recent_window=16,
        manager_cfg=ManagerConfig(policy="analytical", alpha=0.5),
        warm_frac=warm_frac,
    )


def fill_cache(cache: TieredKVCache, rng: np.random.Generator, n_pages: int):
    """Append n_pages identical-content pages across (layer, slot, page)."""
    coords = [
        (la, sl, pg)
        for la in range(cache.la)
        for sl in range(cache.bs)
        for pg in range(cache.max_pages)
    ][:n_pages]
    kv, hd = CFG.n_kv_heads, CFG.head_dim_()
    k = rng.normal(0, 1, (len(coords), cache.pt, kv, hd)).astype(np.float32)
    v = rng.normal(0, 1, (len(coords), cache.pt, kv, hd)).astype(np.float32)
    cache.append_pages(coords, jnp.asarray(k), jnp.asarray(v))
    return coords


def logical_content(cache: TieredKVCache):
    """{rid: (placement, (k_pay, k_sc, v_pay, v_sc))} from wherever it lives."""
    st = cache.state
    out = {}
    for rid in np.where(cache._page_exists)[0]:
        rid = int(rid)
        loc = int(cache.physical[rid])
        ps = int(cache._pool_slot[rid])
        if loc in (WARM, COLD):
            # Payloads live in the shared codec-class buffers; slots are
            # global class rows.
            cls = cache._cls["warm" if loc == WARM else "cold"]
            item = (getattr(st, f"{cls}_k")[ps],
                    getattr(st, f"{cls}_k_scales")[ps],
                    getattr(st, f"{cls}_v")[ps],
                    getattr(st, f"{cls}_v_scales")[ps])
        else:
            item = cache.host_pages[rid]
        out[rid] = (loc, tuple(np.asarray(x) for x in item))
    return out


def check_table_invariants(cache: TieredKVCache):
    """Every pooled page appears exactly once in its (layer, slot) table row;
    rows contain nothing else; free lists are disjoint from live slots."""
    st = cache.state
    for pool, level in (("warm", WARM), ("cold", COLD)):
        table = np.asarray(getattr(st, f"{pool}_table"))
        nvec = np.asarray(getattr(st, f"{pool}_n"))
        want = {}
        for rid in np.where((cache.physical == level) & cache._page_exists)[0]:
            layer, slot, _ = cache.rid_coords(int(rid))
            want.setdefault((layer, slot), []).append(int(cache._pool_slot[rid]))
        for layer in range(cache.la):
            for slot in range(cache.bs):
                n = int(nvec[layer, slot])
                row = sorted(table[layer, slot, :n].tolist())
                assert row == sorted(want.get((layer, slot), [])), (pool, layer, slot)
        live = {int(cache._pool_slot[r])
                for r in np.where((cache.physical == level) & cache._page_exists)[0]}
        free = cache._free_warm if level == WARM else cache._free_cold
        assert not (set(free) & live), pool


def assert_same_state(a: TieredKVCache, b: TieredKVCache):
    np.testing.assert_array_equal(a.physical, b.physical)
    np.testing.assert_array_equal(a.manager.placement, b.manager.placement)
    np.testing.assert_array_equal(a._page_exists, b._page_exists)
    ca, cb = logical_content(a), logical_content(b)
    assert ca.keys() == cb.keys()
    for rid in ca:
        (loc_a, pa), (loc_b, pb) = ca[rid], cb[rid]
        assert loc_a == loc_b, rid
        np.testing.assert_array_equal(pa[0], pb[0], err_msg=f"k payload rid={rid}")
        np.testing.assert_array_equal(pa[2], pb[2], err_msg=f"v payload rid={rid}")
        np.testing.assert_allclose(pa[1], pb[1], rtol=1e-6, err_msg=f"k scales rid={rid}")
        np.testing.assert_allclose(pa[3], pb[3], rtol=1e-6, err_msg=f"v scales rid={rid}")
    assert set(a.host_pages.keys()) == set(b.host_pages.keys())
    check_table_invariants(a)
    check_table_invariants(b)


def random_plan(cache: TieredKVCache, rng: np.random.Generator):
    """A random feasible plan: subset of live pages, random new tiers, with
    WARM inflow bounded so no capacity pressure perturbs either executor."""
    live = np.where(cache._page_exists)[0]
    m = draw_int(rng, 1, len(live))
    rids = rng.choice(live, size=m, replace=False)
    dsts = np.array(
        [rng.choice([t for t in (WARM, COLD, HOST8, HOST4)
                     if t != cache.physical[r]]) for r in rids],
        np.int64,
    )
    budget = len(cache._free_warm) + int((cache.physical[rids] == WARM).sum())
    to_warm = np.where(dsts == WARM)[0]
    for i in to_warm[budget:]:
        dsts[i] = COLD
    keep = dsts != cache.physical[rids]
    return rids[keep], dsts[keep]


# ---------------------------------------------------------------------------
# executor equivalence
# ---------------------------------------------------------------------------


def test_batched_executor_matches_per_page_loop():
    for i, rng in cases(12):
        a, b = make_cache(), make_cache()
        n_pages = draw_int(rng, 4, a.n_regions)
        fill_seed = draw_int(rng, 0, 2**31 - 1)
        fill_cache(a, np.random.default_rng(fill_seed), n_pages)
        fill_cache(b, np.random.default_rng(fill_seed), n_pages)
        assert_same_state(a, b)
        for _ in range(draw_int(rng, 1, 3)):  # chained windows of migrations
            rids, dsts = random_plan(a, rng)
            for rid, dst in zip(rids, dsts):  # per-page oracle, plan order
                a.migrate(int(rid), int(dst))
            moved = b.migrate_batch(rids, dsts)
            assert moved == len(rids), i
            assert_same_state(a, b)


def test_batched_executor_skips_missing_and_noop_pages():
    rng = np.random.default_rng(0)
    c = make_cache()
    fill_cache(c, rng, 6)
    live = np.where(c._page_exists)[0]
    missing = np.where(~c._page_exists)[0][:2]
    rids = np.concatenate([live[:2], missing])
    dsts = np.array([c.physical[live[0]], COLD, WARM, WARM], np.int64)  # first = no-op
    moved = c.migrate_batch(rids, dsts)
    assert moved == 1  # only live[1] -> COLD actually moves
    check_table_invariants(c)


def test_batched_executor_dedups_repeated_rids_last_wins():
    """Repeated rids in one plan must not crash or double-free slots: the
    page lands at its LAST dst (where a sequential loop would leave it).
    Content is not compared against the sequential replay — the batch jumps
    straight to the final tier and so skips the loop's lossy intermediate
    int4 hop."""
    c = make_cache()
    fill_cache(c, np.random.default_rng(11), 8)
    r = int(np.where(c._page_exists)[0][0])
    warm_free_before = len(c._free_warm)
    moved = c.migrate_batch(
        np.array([r, r, r], np.int64), np.array([HOST4, COLD, HOST8], np.int64)
    )
    assert moved == 1
    assert int(c.physical[r]) == HOST8
    assert int(c.manager.placement[r]) == HOST8
    assert r in c.host_pages
    assert len(c._free_warm) == warm_free_before + 1  # freed exactly once
    check_table_invariants(c)


def test_batched_executor_spills_warm_overflow_to_cold():
    rng = np.random.default_rng(1)
    c = make_cache(warm_frac=0.25)  # warm pool: 8 slots of 32 pages
    fill_cache(c, rng, 24)  # 8 land warm, 16 spill cold at ingest
    cold = np.where((c.physical == COLD) & c._page_exists)[0]
    # Ask for more promotions than the warm pool can ever hold.
    moved = c.migrate_batch(cold, np.full(cold.size, WARM, np.int64))
    assert moved > 0
    assert (c.physical[c._page_exists] > 0).all()
    assert int((c.physical == WARM).sum()) <= 8
    # manager placement reflects where pages actually landed (spills included).
    np.testing.assert_array_equal(c.physical, c.manager.placement)
    check_table_invariants(c)


def test_end_window_reconciles_physical_with_plan():
    rng = np.random.default_rng(2)
    c = make_cache()
    fill_cache(c, rng, 16)
    for _ in range(3):
        counts = np.zeros(c.n_regions)
        live = np.where(c._page_exists)[0]
        counts[rng.choice(live, size=8, replace=False)] = rng.integers(1, 100, 8)
        c.manager.record_access_counts(counts)
        plan, moved = c.end_window()
        assert moved >= 0
        # Existing pages: desired == actual. (Non-existent regions keep the
        # policy's fantasy placement; the cost model only prices existing.)
        ex = c._page_exists
        np.testing.assert_array_equal(c.physical[ex], c.manager.placement[ex])
        assert not ((c.physical == 0) & ex).any()  # never "DRAM"
        check_table_invariants(c)


# ---------------------------------------------------------------------------
# dispatch accounting (the O(pages) -> O(cohorts) claim)
# ---------------------------------------------------------------------------


def test_batched_dispatches_at_least_5x_fewer_at_256_pages():
    a = make_cache(layers=4, slots=4, page_tokens=8, max_seq=128, warm_frac=1.0)
    b = make_cache(layers=4, slots=4, page_tokens=8, max_seq=128, warm_frac=1.0)
    assert a.n_regions == 256
    fill_cache(a, np.random.default_rng(7), 256)
    fill_cache(b, np.random.default_rng(7), 256)
    rids = np.where(a._page_exists)[0]
    dsts = np.where(np.arange(rids.size) % 2 == 0, COLD, HOST4).astype(np.int64)

    a.kernel_dispatches = 0
    for rid, dst in zip(rids, dsts):
        a.migrate(int(rid), int(dst))
    per_page = a.kernel_dispatches

    b.kernel_dispatches = 0
    b.migrate_batch(rids, dsts)
    batched = b.kernel_dispatches

    assert batched * 5 <= per_page, (batched, per_page)
    assert_same_state(a, b)


# ---------------------------------------------------------------------------
# vectorized plan pricing == per-page reference loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("config", ["6T-AM-0.5", "6T-WF-M", "2T-M"])
def test_plan_vectorized_matches_loop(config):
    for i, rng in cases(50):
        mgr = make_manager(config, 64)
        m = draw_int(rng, 0, 64)
        regions = rng.choice(64, size=m, replace=False)
        n_opts = mgr.tierset.n_tiers + 1
        src = rng.integers(0, n_opts, m)
        dst = (src + rng.integers(1, n_opts, m)) % n_opts  # always a real move
        vec = mgr._plan(regions, src, dst)
        ref = mgr._plan_loop(regions, src, dst)
        assert vec.bytes_moved == ref.bytes_moved, i
        assert vec.modeled_migration_s == pytest.approx(ref.modeled_migration_s, rel=1e-12), i
        if m:
            assert vec.n_cohorts == len({(int(s), int(d)) for s, d in zip(src, dst)}), i
        else:
            assert vec.n_cohorts == 0


def test_plan_same_codec_fast_path_priced_as_copy():
    """C5(int8-HBM) <-> C7(int8-host) share a codec: the plan must price the
    move as two media copies, strictly cheaper than a transcode route."""
    mgr = make_manager("6T-AM-0.5", 8)
    ts = mgr.tierset
    pairs = [
        (i + 1, j + 1)
        for i, a in enumerate(ts.tiers)
        for j, b in enumerate(ts.tiers)
        if i != j and a.codec_name == b.codec_name
    ]
    assert pairs, "selected tierset should contain at least one same-codec pair"
    for s, d in pairs:
        one = mgr._plan(np.array([0]), np.array([s]), np.array([d]))
        copy_s = (mgr._stored_bytes[s] + mgr._stored_bytes[d]) / 819e9
        assert one.modeled_migration_s == pytest.approx(float(copy_s))
        transcode_s = mgr._lat_region[s] + mgr._compress_lat[d]
        assert one.modeled_migration_s < transcode_s
