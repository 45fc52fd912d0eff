"""K-Means jobs: k distinct rows drawn from the job's key by
``jax.random.choice``, unit-normalized, are the initial centers; then
spherical K-Means for ``iters`` iterations. Compared by
``reference.kmeans_numbers``."""

import reference as ref


def _init(x, key, k):
    return ref.l2n(x[ref.sample_choice(key, n=x.shape[0], s=k)])


def run(x, key, p, *, lower=False):
    return ref.kmeans_job(x, _init(x, key, p["k"]), p, lower=lower)


def numbers(x, key, out, p) -> dict[str, float]:
    return ref.kmeans_numbers(x, _init(x, key, p["k"]), out, p)
