"""The held experts' two paths (`ops/pallas/moe_experts.py`) on the CPU,
their kernels under the Pallas interpreter: the direct path (a step's own
rows, every group within one tile) against the grouped reference
(`grouped_matmul_reference` over `plan`'s buffer), and both against a
plain float32 sum over each token's held experts; which shapes take which
path; and what the direct path leaves out of the dispatch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401 — turns on jax_enable_x64, as every user does
from paddle_tpu.models import exaone_moe
from paddle_tpu.ops.pallas.moe_experts import (experts_ffn, route_direct,
                                               takes_direct)

WIDTH, EXPERT, PUBLISHED, TOP_K = 256, 128, 12, 3
FIRST, HELD = 4, 6
#: max |got - want| over max |want|, as `chip_smoke.py` holds the kernels
TOL = 2e-2


def _weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    gate_up = 0.05 * jax.random.normal(ks[0], (HELD, WIDTH, 2 * EXPERT))
    down = 0.05 * jax.random.normal(ks[1], (HELD, EXPERT, WIDTH))
    return gate_up.astype(jnp.bfloat16), down.astype(jnp.bfloat16)


def _routing(case, t):
    """(experts [t, top_k], weights [t, top_k]) of a case: ids over the
    layer's PUBLISHED experts, of which FIRST .. FIRST + HELD are held."""
    rng = np.random.RandomState(t)
    if case == "unheld_only":
        pool = [e for e in range(PUBLISHED)
                if not FIRST <= e < FIRST + HELD]
    elif case == "some_held_idle":
        pool = [e for e in range(PUBLISHED) if e not in (FIRST, FIRST + 3)]
    else:
        pool = list(range(PUBLISHED))
    experts = np.stack([rng.choice(pool, TOP_K, replace=False)
                        for _ in range(t)]).astype(np.int32)
    if case == "invalid_rows":
        experts[::3] = -1               # as `moe_ffn` marks a row not valid
    weights = rng.uniform(0.1, 1.0, (t, TOP_K)).astype(np.float32)
    return experts, weights


def _plain(x, weights, experts, gate_up, down):
    """sum_j weights[t, j] * E_j(x[t]) over the held experts, float32."""
    x = np.asarray(x, np.float32)
    gu, dn = np.asarray(gate_up, np.float32), np.asarray(down, np.float32)
    out = np.zeros_like(x)
    for e in range(HELD):
        w = np.where(experts == FIRST + e, weights, 0.0).sum(1)[:, None]
        h = x @ gu[e]
        a = h[:, :EXPERT] / (1.0 + np.exp(-h[:, :EXPERT])) * h[:, EXPERT:]
        out += w * (a @ dn[e])
    return out


def _close(got, want):
    err = np.abs(np.asarray(got) - want).max() / (np.abs(want).max() + 1e-30)
    assert np.isfinite(np.asarray(got)).all() and err <= TOL, err


def _direct_and_grouped(t, case):
    experts, weights = _routing(case, t)
    x = jax.random.normal(jax.random.PRNGKey(t), (t, WIDTH)).astype(
        jnp.bfloat16)
    gate_up, down = _weights()
    run = jax.jit(experts_ffn, static_argnames=("first", "backend"))
    direct = run(x, jnp.asarray(weights), jnp.asarray(experts), gate_up,
                 down, first=FIRST, backend="interpret")
    grouped = run(x, jnp.asarray(weights), jnp.asarray(experts), gate_up,
                  down, first=FIRST, backend="reference")
    return direct, grouped, _plain(x, weights, experts, gate_up, down), \
        experts


CASES = ([("spread", t) for t in (1, 32, 96, 128)]
         + [(case, 32) for case in ("some_held_idle", "unheld_only",
                                    "invalid_rows")]
         + [("group_limited", 32), ("grouped_at_192", 192),
            ("grouped_at_2048", 2048)])


@pytest.mark.parametrize("case,t", CASES, ids=[f"{c}-{t}" for c, t in CASES])
def test_the_direct_path_is_the_grouped_one(case, t):
    if case == "group_limited":
        _group_limited_router(t)
        return
    if case.startswith("grouped_at"):
        _grouped_beyond_a_tile(t)
        return
    assert takes_direct(t, "interpret") and not takes_direct(t, "reference")
    (out_d, sizes_d), (out_g, sizes_g), plain, experts = \
        _direct_and_grouped(t, case)
    held = (experts >= FIRST) & (experts < FIRST + HELD)
    want_sizes = [int((experts == FIRST + e).sum()) for e in range(HELD)]
    assert np.asarray(sizes_d).tolist() == want_sizes
    assert np.asarray(sizes_g).tolist() == want_sizes
    assert int(np.asarray(sizes_d).sum()) == int(held.sum())
    if not held.any():
        assert not np.asarray(out_d).any() and not np.asarray(out_g).any()
        return
    _close(out_d, np.asarray(out_g))
    _close(out_d, plain)
    _close(out_g, plain)
    if case == "some_held_idle":
        r = route_direct(jnp.asarray(experts), jnp.ones(experts.shape),
                         FIRST, HELD)
        n = int(r.n_used[0])
        assert n == HELD - 2
        assert np.asarray(r.used)[:n].tolist() == [1, 2, 4, 5]
    if case == "invalid_rows":
        assert not np.asarray(out_d)[::3].any()


def _group_limited_router(t):
    """A softmax router over four groups of three experts that keeps two:
    `moe_ffn`'s group sizes (with the tokens whose kept groups hold a
    held expert last) and output are the same on both paths."""
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    gate_up, down = _weights(1)
    lp = dict(router=0.5 * jax.random.normal(ks[0], (WIDTH, PUBLISHED)),
              gate_up=gate_up, down=down,
              wg=0.05 * jax.random.normal(ks[1], (WIDTH, EXPERT)),
              wu=0.05 * jax.random.normal(ks[2], (WIDTH, EXPERT)),
              wd=0.05 * jax.random.normal(ks[3], (EXPERT, WIDTH)))
    st = dict(top_k=TOP_K, scale=1.0, norm_topk=False, first=FIRST,
              scoring="softmax", n_group=4, topk_group=2)
    x = jax.random.normal(ks[4], (t, WIDTH)).astype(jnp.bfloat16)
    valid = jnp.arange(t) % 5 != 0
    out = {b: jax.jit(lambda h, v, b=b: exaone_moe.moe_ffn(
        h, lp, st, jnp.bfloat16, valid=v, backend=b))(x, valid)
        for b in ("interpret", "reference")}
    (out_d, sizes_d), (out_g, sizes_g) = out["interpret"], out["reference"]
    assert sizes_d.shape == (HELD + 1,)
    assert np.asarray(sizes_d).tolist() == np.asarray(sizes_g).tolist()
    assert 0 < int(sizes_d[:HELD].sum()) <= int(valid.sum()) * TOP_K
    _close(np.asarray(out_d, np.float32), np.asarray(out_g, np.float32))


def _grouped_beyond_a_tile(t):
    """More rows than a tile keep the grouped buffer: its sort is in the
    program, where a decode step's direct one has none."""
    assert not takes_direct(t, "interpret") and not takes_direct(t, "kernel")
    experts, weights = _routing("spread", t)
    gate_up, down = _weights()

    def prog(rows):
        return jax.make_jaxpr(lambda x, w, e: experts_ffn(
            x, w, e, gate_up, down, first=FIRST, backend="interpret"))(
            jnp.zeros((rows, WIDTH), jnp.bfloat16),
            jnp.asarray(weights[:rows]), jnp.asarray(experts[:rows]))

    def ops(jaxpr):
        found = set()
        for eqn in jaxpr.eqns:
            found.add(eqn.primitive.name)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)     # a ClosedJaxpr's
                    if hasattr(sub, "eqns"):
                        found |= ops(sub)
        return found

    grouped, direct = ops(prog(t).jaxpr), ops(prog(128).jaxpr)
    assert "sort" in grouped and "scatter" in grouped
    assert not {"sort", "scatter", "scatter-add", "gather"} & direct
    if t <= 192:      # the interpreter's product beside the reference
        (out_g, sizes_g), (out_r, sizes_r), plain, _ = \
            _direct_and_grouped(t, "spread")
        assert np.asarray(sizes_g).tolist() == np.asarray(sizes_r).tolist()
        _close(out_g, np.asarray(out_r))
        _close(out_g, plain)
