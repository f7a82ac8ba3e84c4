"""Deployment tables, made on the device from the seed in one jitted call.

The benchmark's own copy of the paper's table construction (BoomHQ §4,
Table 1), independent of the program's generators:

* ``scalars_then_embed`` (s->v, the TPC-H tables): scalar columns drawn from
  their marginals (truncated Zipf categoricals, lognormal and uniform
  numerics), then each vector column a fixed random two-layer tanh feature
  map of the standardised scalar row plus Gaussian noise, L2-normalised, so
  nearby scalar rows get nearby vectors.
* ``mixture_then_augment`` (v->s, the ANN sets): Gaussian-mixture vectors,
  then the three correlated scalar constructions: the k-means cluster id,
  the side-of-random-hyperplanes code and the sum of distances to random
  reference points.

A config's ``table`` object names the generator and its parameters.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass
class Data:
    vectors: list  # (n, d_i) f32 device arrays
    scalars: jax.Array  # (n, M) f32 on the device
    host_scalars: np.ndarray  # the same, on the host
    scalar_cols: list  # [{"name", "kind", "n_categories"}]
    vector_cols: list  # [{"name", "dim"}]
    metric: str

    @property
    def n_rows(self) -> int:
        return int(self.scalars.shape[0])


def seed_words(seed: int, *tags: int) -> np.random.SeedSequence:
    """A seed sequence for ``seed`` (any whole number) and a stream tag."""
    return np.random.SeedSequence([seed % (1 << 64), *tags])


def jax_key(seed: int, tag: int) -> jax.Array:
    return jax.random.PRNGKey(int(seed_words(seed, tag).generate_state(1)[0]))


def zipf_logits(a: float, n_categories: int) -> np.ndarray:
    """log P(min(X - 1, m - 1) = j) for X ~ Zipf(a), j = 0..m-1: the last
    category takes the whole tail, as ``numpy``'s zipf clipped at m - 1."""
    k = np.arange(1, 2_000_001, dtype=np.float64)
    big_n = k[-1]
    # zeta(a): the partial sum and the Euler-Maclaurin tail
    zeta = np.sum(k ** -a) + big_n ** (1 - a) / (a - 1) - 0.5 * big_n ** -a
    p = (np.arange(1, n_categories + 1, dtype=np.float64) ** -a) / zeta
    p[-1] = max(1.0 - p[:-1].sum(), 1e-300)
    return np.log(p).astype(np.float32)


def _scalar_column(key, spec: dict, n: int):
    dist = spec["dist"]
    if dist == "zipf":
        logits = jnp.asarray(zipf_logits(spec["a"], spec["n_categories"]))
        return jax.random.categorical(key, logits, shape=(n,)).astype(
            jnp.float32)
    if dist == "lognormal":
        return jnp.exp(spec["mean"] + spec["sigma"]
                       * jax.random.normal(key, (n,), jnp.float32))
    if dist == "uniform":
        return jax.random.uniform(key, (n,), jnp.float32, spec["lo"],
                                  spec["hi"])
    raise ValueError(f"unknown scalar distribution {dist!r}")


def _hash_embed(key, scalars, dim: int, noise: float):
    k1, k2, k3 = jax.random.split(key, 3)
    n, m = scalars.shape
    h_dim = 4 * m + 8
    z = (scalars - scalars.mean(0)) / (scalars.std(0) + 1e-6)
    w1 = jax.random.normal(k1, (m, h_dim), jnp.float32)
    w2 = jax.random.normal(k2, (h_dim, dim), jnp.float32) / np.sqrt(h_dim)
    h = jnp.tanh(jnp.matmul(z, w1, precision=HIGHEST))
    v = jnp.tanh(jnp.matmul(h, w2, precision=HIGHEST)) \
        + noise * jax.random.normal(k3, (n, dim), jnp.float32)
    return v / (jnp.linalg.norm(v, axis=1, keepdims=True) + 1e-9)


@partial(jax.jit, static_argnames=("n", "scalar_specs", "vector_specs"))
def _scalars_then_embed(key, *, n, scalar_specs, vector_specs):
    ks, kv = jax.random.split(key)
    cols = [_scalar_column(k, dict(s), n)
            for k, s in zip(jax.random.split(ks, len(scalar_specs)),
                            scalar_specs)]
    scalars = jnp.stack(cols, axis=1)
    vecs = [_hash_embed(k, scalars, dict(v)["dim"], dict(v)["noise"])
            for k, v in zip(jax.random.split(kv, len(vector_specs)),
                            vector_specs)]
    return vecs, scalars


def _kmeans_assign(key, v, n_clusters: int, iters: int):
    cent = v[jax.random.choice(key, v.shape[0], (n_clusters,), replace=False)]

    def assign(c):
        d = jnp.sum(c * c, 1)[None] - 2.0 * jnp.matmul(v, c.T,
                                                       precision=HIGHEST)
        return jnp.argmin(d, axis=1)

    def step(c, _):
        one = jax.nn.one_hot(assign(c), n_clusters, dtype=jnp.float32)
        cnt = one.sum(0)
        new = jnp.matmul(one.T, v, precision=HIGHEST) \
            / jnp.maximum(cnt[:, None], 1.0)
        return jnp.where(cnt[:, None] > 0, new, c), None

    cent, _ = jax.lax.scan(step, cent, None, length=iters)
    return assign(cent).astype(jnp.float32)


def _hyperplane_code(key, v, n_planes: int):
    planes = jax.random.normal(key, (v.shape[1], n_planes), jnp.float32)
    bits = (jnp.matmul(v, planes, precision=HIGHEST) > 0).astype(jnp.int32)
    weights = 2 ** jnp.arange(n_planes - 1, -1, -1, dtype=jnp.int32)
    return jnp.sum(bits * weights, axis=1).astype(jnp.float32)


def _ref_distance_sum(key, v, n_refs: int):
    lo, hi = v.min(0), v.max(0)
    refs = jax.random.uniform(key, (n_refs, v.shape[1]), jnp.float32) \
        * (hi - lo) + lo
    d2 = jnp.sum(v * v, 1)[:, None] + jnp.sum(refs * refs, 1)[None] \
        - 2.0 * jnp.matmul(v, refs.T, precision=HIGHEST)
    return jnp.sum(jnp.sqrt(jnp.maximum(d2, 0.0)), axis=1)


@partial(jax.jit, static_argnames=("n", "vector_specs", "scalar_specs"))
def _mixture_then_augment(key, *, n, vector_specs, scalar_specs):
    kv, ks = jax.random.split(key)
    vecs = []
    for k, spec in zip(jax.random.split(kv, len(vector_specs)), vector_specs):
        spec = dict(spec)
        k1, k2, k3 = jax.random.split(k, 3)
        mus = jax.random.normal(k1, (spec["components"], spec["dim"]),
                                jnp.float32)
        comp = jax.random.randint(k2, (n,), 0, spec["components"])
        vecs.append(mus[comp] + spec["spread"] * jax.random.normal(
            k3, (n, spec["dim"]), jnp.float32))
    cols = []
    for k, spec in zip(jax.random.split(ks, len(scalar_specs)), scalar_specs):
        spec = dict(spec)
        v = vecs[0]
        if spec["construct"] == "kmeans_cluster":
            cols.append(_kmeans_assign(k, v, spec["n_categories"],
                                       spec["iters"]))
        elif spec["construct"] == "hyperplane_code":
            cols.append(_hyperplane_code(k, v, spec["planes"]))
        elif spec["construct"] == "ref_distance_sum":
            cols.append(_ref_distance_sum(k, v, spec["refs"]))
        else:
            raise ValueError(f"unknown construction {spec['construct']!r}")
    return vecs, jnp.stack(cols, axis=1)


GENERATORS = {
    "scalars_then_embed": _scalars_then_embed,
    "mixture_then_augment": _mixture_then_augment,
}


def _frozen(specs: list) -> tuple:
    return tuple(tuple(sorted(s.items())) for s in specs)


def make(table: dict, seed: int) -> Data:
    """The table a config's ``table`` object describes, for ``seed``."""
    gen = GENERATORS[table["generator"]]
    vecs, scalars = gen(jax_key(seed, 1), n=int(table["rows"]),
                        scalar_specs=_frozen(table["scalars"]),
                        vector_specs=_frozen(table["vectors"]))
    jax.block_until_ready((vecs, scalars))
    scalar_cols = [{"name": s["name"], "kind": s["kind"],
                    "n_categories": int(s.get("n_categories", 0))}
                   for s in table["scalars"]]
    vector_cols = [{"name": v["name"], "dim": int(v["dim"])}
                   for v in table["vectors"]]
    return Data(list(vecs), scalars, np.asarray(scalars), scalar_cols,
                vector_cols, table["metric"])
