"""``correct`` on a CPU-sized run of the harness: a sound run passes, and a
run whose timed path is broken underneath fails.

The chip check is skipped; everything else is the run as ``run.py`` makes
it: set-up traffic, the timed window, the sample, the float32 reference
and the cell's own limits.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.tests.smoke import smoke_cell


def stale_state(eng):
    """The decode step hands back the state it was given."""
    step = eng._step_fn

    def unchanged(params, tokens, tkv, ssm):
        logits, _, _, telemetry = step(params, tokens, tkv, ssm)
        return logits, tkv, ssm, telemetry

    eng._step_fn = unchanged


def altered_token(eng):
    """Every slot's token is replaced as the step produces it."""
    decode = eng._decode_step

    def altered():
        decode()
        for req in eng.slots:
            if req is not None and req.out_tokens:
                req.out_tokens[-1] = (req.out_tokens[-1] + 1) % eng.cfg.vocab_size

    eng._decode_step = altered


def swapped_pages(eng):
    """The int8 pages of slots 0 and 1 hold each other's contents, as a
    migration that writes pages back to the wrong request would leave them.
    The page tables stay as the program keeps them, so that its own moves of
    those pages later in the window still find them."""
    st = eng.cache.state
    t, n = np.asarray(st.warm_table), np.asarray(st.warm_n)
    k = np.minimum(n[:, 0], n[:, 1])
    a = jnp.asarray(np.concatenate([t[la, 0, :k[la]] for la in range(t.shape[0])]))
    b = jnp.asarray(np.concatenate([t[la, 1, :k[la]] for la in range(t.shape[0])]))
    swapped = {}
    for f in ("c8_k", "c8_k_scales", "c8_v", "c8_v_scales"):
        x = getattr(st, f)
        swapped[f] = x.at[a].set(x[b]).at[b].set(x[a])
    eng.cache.state = dataclasses.replace(st, **swapped)


def int4_half_lost(eng):
    """Every int4 page, on the device or the host, holds the first half of
    each head in both nibbles, as a packing that writes one half twice
    would leave it; applied after every step."""
    step = eng.step

    def lose(p):
        low = p & 0xF
        return low | (low << 4)

    def broken():
        step()
        cache = eng.cache
        for rid, (kp, ks, vp, vs) in list(cache.host_pages.items()):
            if np.asarray(kp).dtype == np.uint8:
                cache.host_pages[rid] = (lose(np.asarray(kp)), ks, lose(np.asarray(vp)), vs)
        st = cache.state
        cache.state = dataclasses.replace(st, c4_k=lose(st.c4_k), c4_v=lose(st.c4_v))

    eng.step = broken


# The faults a serving cell can have (one chip, no training step): its
# state handed back unchanged, a token altered where it is produced, and
# KV pages whose contents are wrong: another request's, or an int4 page
# that lost half of each head.
CASES = [
    ("internlm2_20b_8l.longctx", None, True),
    ("internlm2_20b_8l.longctx", stale_state, False),
    ("internlm2_20b_8l.longctx", altered_token, False),
    ("internlm2_20b_8l.longctx", swapped_pages, False),
    ("internlm2_20b_8l.longctx", int4_half_lost, False),
]


@pytest.mark.parametrize("cell_name,fault,sound", CASES,
                         ids=[f"{c}-{f.__name__ if f else 'sound'}" for c, f, _ in CASES])
def test_correct(cell_name, fault, sound):
    cell = smoke_cell(cell_name, window_steps=64)
    run, c, _ = harness.measure(cell, 2**32 + 11, 3.0, False, jax.devices()[0], None,
                                before_window=fault)
    assert c["compared_tokens"]["value"] >= c["compared_tokens"]["limit"]
    if fault in (None, int4_half_lost):
        assert run.pages.by_bits.get(4, 0) > 0  # the sample holds int4 pages
    assert harness.passed(c) is sound, c
