"""The xLSTM population forward's sLSTM recurrence against the menu rows of
its bank (``xlstm_target.forward_population``).

Above K = 4 lanes the banked forward no longer gathers each lane's
recurrent kernel ``r``: every step contracts all lanes' states against the
K bank rows in one dot and each lane selects its own row. At K lanes or
fewer it still gathers. Checked on both sides of K, bit for bit on the
CPU: each lane's logits equal the plain per-lane ``forward`` with that
lane's gathered weights, and the error rates equal the requant lane's. A
bank row that overflows leaves the lanes that do not select it finite,
which a one-hot multiply-and-sum would not (0 x inf is NaN).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import batched_eval as BE
from repro.core import quantization as Q
from repro.core import xlstm_target as XT

LANES = [1, 3, 4, 5, 8, 16]
K = len(Q.SUPPORTED_BITS)


@pytest.fixture(scope="module")
def xlstm():
    return XT.train_small_xlstm(steps=60)


@pytest.fixture(scope="module")
def banks(xlstm):
    return xlstm.make_banks(xlstm.params)


@pytest.fixture(scope="module")
def tokens(xlstm):
    return jnp.concatenate([t for t, _ in xlstm.val_subsets])


@pytest.fixture(scope="module")
def per_lane(xlstm, tokens):
    """The plain forward of one lane: its gathered weights ``ws`` and its
    (L, 6) qp row, unbatched."""
    li = {n: i for i, n in enumerate(xlstm.layer_names)}

    @jax.jit
    def run(ws, row):
        def q_act(name, x):
            r = row[li[name]]
            return Q.fake_quant_triple(x, r[3], r[4], r[5])
        return XT.forward(xlstm.params, xlstm.cfg, tokens,
                          lambda name: ws[name], q_act)
    return run


def _allocs(xlstm, lanes, seed):
    rng = np.random.default_rng(seed)
    menu = list(xlstm.menu)
    return [{n: (menu[rng.integers(K)], menu[rng.integers(K)])
             for n in xlstm.layer_names} for _ in range(lanes)]


def _stack(xlstm, allocs):
    return jnp.asarray(BE.stack_qps([xlstm.qp_for(a) for a in allocs],
                                    list(xlstm.layer_names)))


def _gathered(xlstm, banks, row):
    """A lane's weights, gathered from the banks by its menu index."""
    return {n: {k: b[int(Q.menu_index_from_hi(row[i, 2]))]
                for k, b in banks[n].items()}
            for i, n in enumerate(xlstm.layer_names)}


def _shapes(jaxpr):
    """Every intermediate shape of a jaxpr, nested bodies included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(v.aval.shape)
        for p in eqn.params.values():
            sub = getattr(p, "jaxpr", p)
            if hasattr(sub, "eqns"):
                yield from _shapes(sub)


def _population(xlstm, tokens, stack, banks):
    return jax.jit(lambda s, b: XT.forward_population(
        xlstm.params, xlstm.cfg, tokens, s, banks=b))(stack, banks)


@pytest.mark.parametrize("lanes", LANES)
def test_banked_logits_equal_per_lane_forward(xlstm, banks, tokens,
                                              per_lane, lanes):
    allocs = _allocs(xlstm, lanes, seed=lanes)
    stack = _stack(xlstm, allocs)
    assert XT.slstm_menu_engaged(banks, lanes) == (lanes > K)
    # the gathered per-lane copy of r exists exactly where the menu
    # contraction is not taken
    r_shape = banks["s0"]["r"].shape[1:]
    jx = jax.make_jaxpr(lambda s: XT.forward_population(
        xlstm.params, xlstm.cfg, tokens, s, banks=banks))(stack)
    assert ((lanes,) + r_shape in set(_shapes(jx.jaxpr))) == (lanes <= K)
    out = _population(xlstm, tokens, stack, banks)
    for p in range(lanes):
        want = per_lane(_gathered(xlstm, banks, stack[p]), stack[p])
        assert jnp.array_equal(out[p], want), f"lane {p} of {lanes}"


@pytest.mark.parametrize("lanes", LANES)
def test_banked_errors_equal_requant(xlstm, lanes):
    allocs = _allocs(xlstm, lanes, seed=100 + lanes)
    banked = xlstm.val_error_batch(allocs)
    requant = xlstm.val_error_batch(allocs, use_banks=False)
    assert banked == requant


@pytest.mark.parametrize("lanes", LANES)
def test_overflowed_row_leaves_other_lanes_finite(xlstm, banks, tokens,
                                                  per_lane, lanes):
    """Row K-1 (16 bits) of the first sLSTM bank overflowed to inf; only
    the last lane selects it. That lane's state turns non-finite; every
    other lane is finite and still equals its per-lane forward."""
    r = banks["s0"]["r"]
    bad = {**banks, "s0": {**banks["s0"], "r": r.at[K - 1].set(jnp.inf)}}
    menu = list(xlstm.menu)
    allocs = _allocs(xlstm, lanes, seed=200 + lanes)
    for p, a in enumerate(allocs):
        a["s0"] = (menu[K - 1] if p == lanes - 1 else menu[p % (K - 1)],
                   a["s0"][1])
    stack = _stack(xlstm, allocs)
    out = _population(xlstm, tokens, stack, bad)
    assert not bool(jnp.isfinite(out[lanes - 1]).all())
    for p in range(lanes - 1):
        assert bool(jnp.isfinite(out[p]).all()), f"lane {p} of {lanes}"
        want = per_lane(_gathered(xlstm, bad, stack[p]), stack[p])
        assert jnp.array_equal(out[p], want), f"lane {p} of {lanes}"
