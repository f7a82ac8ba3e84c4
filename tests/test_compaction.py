"""Candidate compaction (``flat.compact_rows``): bit-identical to
``jnp.nonzero(mask, size=S, fill_value=f)[0]`` by either method, and the
static shape rule that picks the method."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gather_score import GatherRows
from repro.vectordb import flat
from repro.vectordb.predicates import Predicates, stack

NS = (1, 127, 128, 129, 1000, 4099)
MASKS = ("empty", "first", "last", "pct1", "half", "full", "one_mid")


def _mask(kind: str, n: int, seed: int = 0) -> np.ndarray:
    m = np.zeros(n, bool)
    if kind == "first":
        m[0] = True
    elif kind == "last":
        m[-1] = True
    elif kind == "one_mid":
        m[n // 2] = True
    elif kind == "full":
        m[:] = True
    elif kind in ("pct1", "half"):
        rng = np.random.default_rng(seed + n)
        m = rng.random(n) < (0.01 if kind == "pct1" else 0.5)
    return m


def _cases():
    """42 of the (n, S, mask, fill) grid: every n with every mask kind,
    S and fill cycling so each S in {1, 16, n, 2n} and each fill in
    {-1, n} meets every n."""
    out = []
    for i, (n, kind) in enumerate((n, k) for n in NS for k in MASKS):
        size = (1, 16, n, 2 * n)[i % 4]
        fill = (-1, n)[(i // 4) % 2]
        out.append(pytest.param(n, size, kind, fill,
                                id=f"n{n}-S{size}-{kind}-f{fill}"))
    return out


@partial(jax.jit, static_argnames=("fn", "size", "fill"))
def _run(masks, *, fn, size, fill):
    return jax.vmap(lambda m: fn(m, size, fill))(masks)


@pytest.mark.parametrize("n,size,kind,fill", _cases())
def test_compact_rows_matches_nonzero(n, size, kind, fill):
    """``compact_rows`` and each method, forced by name, give exactly
    ``nonzero``'s int32 rows, order and fill, alone and under vmap over a
    batch of 3 masks."""
    one = jnp.asarray(_mask(kind, n))
    batch = jnp.stack([one, ~one, jnp.asarray(_mask("half", n, seed=1))])
    fns = {"compact_rows": flat.compact_rows, **flat.COMPACTIONS}
    want = np.asarray(jnp.nonzero(one, size=size, fill_value=fill)[0])
    want_b = np.stack([np.asarray(jnp.nonzero(m, size=size,
                                              fill_value=fill)[0])
                       for m in batch])
    for name, fn in fns.items():
        got = fn(one, size, fill)
        assert got.dtype == jnp.int32, name
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=name)
        got_b = _run(batch, fn=fn, size=size, fill=fill)
        assert got_b.dtype == jnp.int32, name
        np.testing.assert_array_equal(np.asarray(got_b), want_b,
                                      err_msg=name)


def _lower_local_batch(n: int, size: int) -> str:
    d = 8
    scalars = jnp.arange(n * 2, dtype=jnp.float32).reshape(n, 2)
    rows = GatherRows.build((jnp.ones((n, d), jnp.float32),), scalars)
    pred_b = stack([Predicates.from_conditions(2, {0: (0.0, 10.0)})] * 2)
    return flat.filter_first_local_batch.lower(
        rows, pred_b, (jnp.ones((2, d), jnp.float32),),
        jnp.ones((2, 1), jnp.float32), k=4, max_candidates=size, n_vec=1,
        use_kernel=False).as_text()


def test_selective_filter_first_lowers_without_scatter():
    """At a selective cap the filter-first program compacts by search: no
    scatter in it, and it keeps its module name; at a cap near the table
    size it keeps ``nonzero``'s scatter."""
    assert flat.compaction_method(65_536, 256) == "search"
    text = _lower_local_batch(65_536, 256)
    assert "module @jit_filter_first_local_batch " in text
    assert "stablehlo.scatter" not in text
    assert flat.compaction_method(64, 16) == "scatter"
    assert "stablehlo.scatter" in _lower_local_batch(64, 16)


@pytest.mark.parametrize("n,size,method", [
    (1_000_000, 16_384, "search"),  # sift_1m's filter-first cap
    (65_536, 256, "search"),
    (64, 16, "scatter"),
    (1, 1, "scatter"),
    (1_000_000, 1_000_000, "scatter"),  # sharded max_candidates = n_rows
    (4099, 4099, "scatter"),
    (4099, 8198, "scatter"),
])
def test_compaction_method_rule(n, size, method):
    assert flat.compaction_method(n, size) == method
