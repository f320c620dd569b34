"""The benchmark's TCO arithmetic (``chipbench/tco.py``) against the
program's ``TieredKVCache.tco_savings_pct`` on a CPU-sized engine."""

import jax
import numpy as np

from chipbench import harness, modelcfg, tco, weights
from chipbench.tests.smoke import smoke_cell

WARM, COLD, HOST8, HOST4 = 1, 2, 3, 4


def _engine():
    cell = smoke_cell("internlm2_20b_8l.longctx")
    arch = modelcfg.arch(cell.config)
    eng = harness.build_engine(cell.config, weights.make(arch, 3), jax.devices()[0])
    rng = np.random.default_rng(0)
    for slot, n in enumerate((64, 96, 130)):
        eng.start_request(slot, eng.make_request(rng.integers(1, arch.vocab, n), 8))
    eng.cache.drain_migrations()
    return cell, arch, eng


def test_layout_bytes():
    costs = tco.load_costs()
    b = tco.level_bytes(costs, 16, 20, 128)
    vals = 16 * 20 * 128
    assert b[0] == 2 * 2 * vals
    assert b[1] == b[3] == 2 * (vals + 16 * 20 * 4)
    assert b[2] == b[4] == 2 * (vals // 2 + 16 * 20 * 4)
    usd = tco.level_usd(costs, 16, 20, 128)
    # every page warm: 1 - (hd + 4) / (2 hd)
    assert np.isclose(tco.savings_pct(np.array([0, 1, 1, 0]), usd), 100 * (1 - 132 / 256))
    assert tco.savings_pct(np.zeros(4, np.int64), usd) is None
    assert tco.savings_pct(np.array([-1, 0]), usd) is None  # only a page in flight


def test_matches_program_where_physical_is_placement():
    cell, arch, eng = _engine()
    cache = eng.cache
    live = np.flatnonzero(cache.physical > 0)
    assert live.size >= 12
    rng = np.random.default_rng(1)
    picks = rng.permutation(live)[:9]
    cache.migrate_batch(picks, np.array([COLD] * 3 + [HOST8] * 3 + [HOST4] * 3, np.int64))
    assert np.array_equal(cache.physical, cache.manager.placement)
    assert set(np.unique(cache.physical[live])) == {WARM, COLD, HOST8, HOST4}
    costs = tco.load_costs()
    e = cell.config["engine"]
    usd = tco.level_usd(costs, int(e["page_tokens"]), arch.n_kv_heads, arch.head_dim)
    # Both sides priced with the same bytes per page: the program's
    # compressibility ratios set to the payload layout's.
    nbytes = tco.level_bytes(costs, int(e["page_tokens"]), arch.n_kv_heads, arch.head_dim)
    cache.manager.measured_ratios[:] = nbytes[0] / nbytes[1:]
    ours = tco.savings_pct(cache.physical, usd)
    assert np.isclose(ours, cache.tco_savings_pct(), rtol=0, atol=1e-9)
    assert 40.0 < ours < 80.0
